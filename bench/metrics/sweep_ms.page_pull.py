"""Milliseconds a scrub sweep spends on the flagged pages before the
drain: finding each page's flagged rows in its mask (in the page pool,
also its per-page RAS scan note), pulling the flagged pages to the host,
copying them and queuing their flagged rows for repair. The
`scrub.page_pull` spans inside the window's `scrub.sweep` spans, per
sweep."""
from lib.scrub_spans import per_sweep


def read(ctx) -> float | None:
    us = per_sweep(ctx, ("scrub.page_pull",))
    return None if us is None else us / 1e3
