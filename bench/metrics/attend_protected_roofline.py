"""Share of the roofline that the fused protected attention kernel
reached in the window: the least time the chip needs for the decode
queries' attention (bf16 peak, HBM bandwidth;
`bench/kernels/attend_protected.py`) over the kernel's device time in
the trace."""
from lib import harness, xplane


def read(ctx) -> float | None:
    w = ctx.window.get("work", {}).get("attend_protected")
    t = xplane.op_seconds(ctx.reduced, "attend_protected")
    if not w or not w["queries"] or t <= 0:
        return None
    ops, nbytes = harness.load_module("kernels", "attend_protected").work(**w)
    t_min = max(ops / ctx.peaks["bf16_flops"],
                nbytes / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * t_min / t
