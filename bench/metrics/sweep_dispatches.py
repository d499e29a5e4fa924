"""Executables a scrub sweep launches: the `dispatches` the program
counts on its `scrub.scan_dispatch` (one scan a page) and
`repair.decode` (one decode a bucket) spans inside the window's
`scrub.sweep` spans, per sweep."""
from lib.scrub_spans import per_sweep


def read(ctx) -> float | None:
    return per_sweep(ctx, ("scrub.scan_dispatch", "repair.decode"),
                     "dispatches")
