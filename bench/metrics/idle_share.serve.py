"""Share of the serving cell's window in which no operation ran on the
device (profiler trace, busy time averaged over the chips used)."""


def read(ctx) -> float | None:
    if ctx.window.get("kind") != "serving":
        return None
    r = ctx.reduced
    return 100.0 * (1.0 - r["busy_s"] / r["window_s"])
