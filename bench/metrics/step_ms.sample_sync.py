"""Milliseconds an engine step spends pulling the sampled tokens to the
host, which waits for the step's device work to finish: the
`engine.sample_sync` spans inside the window's `engine.step` spans, per
step."""
from lib.step_spans import per_step_ms


def read(ctx) -> float | None:
    if ctx.window.get("kind") != "serving":
        return None
    return per_step_ms(ctx, "engine.sample_sync")
