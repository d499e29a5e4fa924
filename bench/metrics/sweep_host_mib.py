"""MiB a scrub sweep moves between host and device: the `bytes` the
program counts on its spans inside the window's `scrub.sweep` spans
(scan masks and flagged pages pulled, decode buckets in and out, pages
written back), per sweep."""
from lib.scrub_spans import per_sweep

SPANS = ("scrub.mask_pull", "scrub.page_pull", "repair.decode",
         "repair.writeback")


def read(ctx) -> float | None:
    nbytes = per_sweep(ctx, SPANS, "bytes")
    return None if nbytes is None else nbytes / 2**20
