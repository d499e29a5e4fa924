"""Share of the chip's bf16 peak that the window's served tokens used:
the model operations of every token decoded and every prompt prefilled
in the window (`bench/kernels/serve_step.py`) over the window's length
times the peak. The whole step's share, which bounds what any one
kernel's speed-up can give `tokens_per_s`."""
from lib import harness


def read(ctx) -> float | None:
    w = ctx.window.get("work", {}).get("serve_step")
    if not w or not (w["contexts"] or w["prompts"]):
        return None
    ops = harness.load_module("kernels", "serve_step").work(**w)
    window = ctx.window["t1"] - ctx.window["t0"]
    return 100.0 * ops / (window * ctx.peaks["bf16_flops"])
