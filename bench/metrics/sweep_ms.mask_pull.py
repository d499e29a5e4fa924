"""Milliseconds a scrub sweep spends pulling the pages' scan masks to the
host, which waits for the scans: the `scrub.mask_pull` spans inside the
window's `scrub.sweep` spans, per sweep."""
from lib.scrub_spans import per_sweep


def read(ctx) -> float | None:
    us = per_sweep(ctx, ("scrub.mask_pull",))
    return None if us is None else us / 1e3
