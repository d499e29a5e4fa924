"""Share of the roofline that the `scan_syndromes` kernel reached in the
window: the least time the chip needs for the scan's work (int8 peak,
HBM bandwidth; `bench/kernels/scan_syndromes.py`) over the kernel's
device time in the trace."""
from lib import harness, xplane


def read(ctx) -> float | None:
    w = ctx.window.get("work", {}).get("scan_syndromes")
    t = xplane.op_seconds(ctx.reduced, "scan_syndromes")
    if not w or t <= 0:
        return None
    ops, nbytes = harness.load_module("kernels", "scan_syndromes").work(**w)
    t_min = max(ops / ctx.peaks["int8_ops"],
                nbytes / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * t_min / t
