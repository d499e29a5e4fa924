"""Per-step sums over the program's serving spans, for the `step_ms.*`
metrics: a span counts toward a step only if it lies under an
`engine.step` (`span_tree.under`). A window with no step, or a step
phase that no step of the window ran, reads nothing.
"""
from __future__ import annotations

from lib.span_tree import under

STEP = "engine.step"


def per_step_ms(ctx, name: str) -> float | None:
    """Milliseconds of the spans named `name` that an `engine.step`
    encloses, over the window's steps. None where no such span fell in
    the window."""
    steps, spans = under(ctx, STEP)
    hits = [e["dur"] for e in spans if e["name"] == name]
    if not hits:
        return None
    return sum(hits) / steps / 1e3
