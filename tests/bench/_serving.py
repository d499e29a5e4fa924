"""A tiny granite-shaped serving cell for the CPU tests: the real
configuration cut to tiny widths, its tables and reference copied beside
it, a tiny closed-loop mix, and the serving system told to build the
program's granite at the same widths."""
import dataclasses
import json
import os
import shutil
import time

import _benchpath
from lib import harness

CONFIGS = os.path.join(_benchpath.BENCH, "configs")
REAL = "granite-3-2b-kv"

# tiny widths; a larger weight scale than the source's 0.02, so that the
# blocks (and not the embedding alone) decide the logits at this width
TINY = {"hidden_size": 64, "num_hidden_layers": 2, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 128,
        "vocab_size": 512, "initializer_range": 0.25, "max_active": 4}
TINY_SERVING = {"max_seq": 96}
# the limits at this size, from CPU readings over seeds 5-8 and 2^32 + 11
# with a 1 s window (sound; the int4 control; the engine rounding K/V
# through fp8 before its int8 pages, seeds 5, 6 and 2^32 + 11):
#   kv_page_offset   sound 0.55-0.74, int4 5.4-6.7, fp8 engine 1.20-1.55
#   last_logit_diff  sound 0.25-0.45, int4 2.0-6.1, fp8 engine 1.12
#   served_logit_gap sound 0.02-0.26, int4 1.4-4.3, fp8 engine 0.49-1.08
TINY_LIMITS = {"kv_page_offset": 0.9, "last_logit_diff": 0.7,
               "served_logit_gap": 0.2}
MIX = {"kind": "requests", "loop": "closed", "clients": 4, "block": 4,
       "sizes_seed": 3,
       "prompt": {"dist": "lognormal", "median": 40, "sigma": 0.3,
                  "min": 24, "max": 64},
       "output": {"dist": "pareto", "min": 4, "median": 8, "max": 24}}
SECONDS = 1.0

SPEC = {"configs": [{"name": "g", "file": "cfg/g.json"}],
        "workloads": [{"name": "longdoc", "config": "g",
                       "traffic": "longdoc", "chips": 1}],
        "end_to_end": [{"name": "tokens_per_s", "unit": "tokens/s",
                        "workloads": ["longdoc"]},
                       {"name": "setup_s", "unit": "s"}],
        "per_layer": []}


def make_root(r) -> str:
    os.makedirs(r / "cfg")
    os.makedirs(r / "traffic")
    with open(os.path.join(CONFIGS, f"{REAL}.json")) as f:
        cfg = json.load(f)
    cfg.update(TINY)
    cfg["serving"].update(TINY_SERVING)
    cfg["limits"] = dict(TINY_LIMITS)
    (r / "cfg" / "g.json").write_text(json.dumps(cfg))
    for key in ("generator_file", "parity_check_file"):
        shutil.copy(os.path.join(CONFIGS, cfg[key]), r / "cfg")
    shutil.copy(os.path.join(CONFIGS, cfg["reference"] + ".py"), r / "cfg")
    (r / "traffic" / "longdoc.json").write_text(json.dumps(MIX))
    return str(r)


def serving_module():
    """The serving system's module, building the program's granite at
    the TINY widths."""
    from repro.configs import get_config
    mod = harness.load_module("systems", "serving")

    def tiny_program_config(config):
        return dataclasses.replace(
            get_config(config["program_config"]), d_model=64, n_groups=2,
            n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128, vocab_size=512)

    mod.program_config = tiny_program_config
    # warm the admissions of a fifth of the window: enough to show the
    # path, without a prefill for every length of the tiny mix
    mod.load_benchmark = lambda root: {"run_seconds": SECONDS / 5}
    return mod


def run(root, factory, seed=2 ** 32 + 11):
    cell = harness.find_cell(SPEC, "longdoc")
    return harness.run_cell(
        root, SPEC, cell, seed=seed, seconds=SECONDS, trace=False,
        device={"platform": "cpu", "kind": "cpu", "count": 1},
        t_start=time.perf_counter(), check_kernels=False,
        system_factory=factory, mixes=root)


def system(root, mod, seed):
    """A tiny system, set up and run for its window."""
    from lib import traffic
    cfg = harness.load_config(root, SPEC, "g")
    s = mod.System(cfg, traffic.load_mix(root, "longdoc"), seed)
    s.setup()
    s.run(SECONDS)
    return s
