"""The program's spans under a root span, by their chain of parents.

The program's `repro.obs.trace.Tracer` records each span with an integer
`id` and the `parent` id of the span that enclosed it. A span lies under
a root if its chain of parents reaches a span of the root's name. A
program whose spans carry no ids reads as holding no root.
"""
from __future__ import annotations


def under(ctx, root: str) -> tuple[int, list[dict]]:
    """(the number of spans named `root`, the spans whose chain of parents
    reaches one of them)."""
    by_id = {e["args"]["id"]: e for e in ctx.spans
             if e.get("ph") == "X" and "id" in e.get("args", {})}
    roots = {i for i, e in by_id.items() if e["name"] == root}

    def reaches(e) -> bool:
        parent = e["args"].get("parent")
        while parent is not None and parent not in roots:
            up = by_id.get(parent)
            parent = None if up is None else up["args"].get("parent")
        return parent is not None

    if not roots:
        return 0, []
    return len(roots), [e for e in by_id.values() if reaches(e)]
