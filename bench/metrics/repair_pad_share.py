"""Share of the rows that the scrub's repair drains sent to the decoder
that were padding (bucketed decode executables), over the window's
sweeps — the program's own drain counters."""


def read(ctx) -> float | None:
    sweeps = ctx.window.get("sweeps")
    if not sweeps:
        return None
    rows = sum(s["dispatch_rows"] for s in sweeps)
    if not rows:
        return None
    return 100.0 * sum(s["pad_rows"] for s in sweeps) / rows
