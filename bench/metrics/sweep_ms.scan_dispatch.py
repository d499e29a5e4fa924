"""Milliseconds a scrub sweep spends launching every page's syndrome
scan (host enqueue, no sync): the `scrub.scan_dispatch` spans inside the
window's `scrub.sweep` spans, per sweep."""
from lib.scrub_spans import per_sweep


def read(ctx) -> float | None:
    us = per_sweep(ctx, ("scrub.scan_dispatch",))
    return None if us is None else us / 1e3
