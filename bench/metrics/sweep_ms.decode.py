"""Milliseconds a scrub sweep spends in the repair queue's bucketed
decode (padding, dispatch and the one pull of the results, so the
device's decode time is inside): the `repair.decode` spans inside the
window's `scrub.sweep` spans, per sweep."""
from lib.scrub_spans import per_sweep


def read(ctx) -> float | None:
    us = per_sweep(ctx, ("repair.decode",))
    return None if us is None else us / 1e3
