"""Per-sweep sums over the program's scrub spans, for the `sweep_*` metrics.

The program's `repro.obs.trace.Tracer` records each span with an integer
`id` and the `parent` id of the span that enclosed it. A span counts
toward a sweep only if its chain of parents reaches a `scrub.sweep`, so
a drain of the repair queue outside a sweep (a serving read-path repair,
say) is never billed to the scrub. A program whose spans carry no ids,
or that records no `scrub.sweep`, reads nothing.
"""
from __future__ import annotations

SWEEP = "scrub.sweep"


def per_sweep(ctx, names: tuple[str, ...], key: str = "dur") -> float | None:
    """Sum of `key` over the spans named in `names` that a `scrub.sweep`
    encloses, over the window's sweeps: `dur` (microseconds) or a counter
    in the span's args. None where the window holds no sweep."""
    by_id = {e["args"]["id"]: e for e in ctx.spans
             if e.get("ph") == "X" and "id" in e.get("args", {})}
    sweeps = {i for i, e in by_id.items() if e["name"] == SWEEP}
    if not sweeps:
        return None

    def in_sweep(e) -> bool:
        parent = e["args"].get("parent")
        while parent is not None and parent not in sweeps:
            up = by_id.get(parent)
            parent = None if up is None else up["args"].get("parent")
        return parent is not None

    total = sum(e["dur"] if key == "dur" else e["args"].get(key, 0)
                for e in by_id.values() if e["name"] in names and in_sweep(e))
    return total / len(sweeps)
