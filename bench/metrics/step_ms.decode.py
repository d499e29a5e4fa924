"""Milliseconds an engine step spends running one batched decode step: the
40 layers' eager dispatch, each protected layer's K/V append, page
freezes and stacked-operand rebuilds, and the fused attention (the span
closes before the device finishes): the `engine.decode` spans inside the
window's `engine.step` spans, per step."""
from lib.step_spans import per_step_ms


def read(ctx) -> float | None:
    if ctx.window.get("kind") != "serving":
        return None
    return per_step_ms(ctx, "engine.decode")
