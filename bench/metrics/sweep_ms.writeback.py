"""Milliseconds a scrub sweep spends writing repaired rows into the host
page copies and enqueuing their re-upload, with the drain's per-entry
owner and RAS bookkeeping: the `repair.writeback` spans inside the
window's `scrub.sweep` spans, per sweep."""
from lib.scrub_spans import per_sweep


def read(ctx) -> float | None:
    us = per_sweep(ctx, ("repair.writeback",))
    return None if us is None else us / 1e3
