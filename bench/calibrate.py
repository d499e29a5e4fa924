#!/usr/bin/env python3
"""Readings that the limits of `correct` are set from (not run by the
benchmark's own runs).

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 20

For each seed, in one process: set the cell up, run its window at the
cell's own load for `--seconds`, and read the checks of a sound run
(`program`) and of a run whose scrubs write nothing back (`control`: the
guarantee the configuration states, broken).

One JSON line per seed on stdout.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))

from lib import harness, traffic  # noqa: E402


def drop_writeback(store) -> None:
    """Break the store's guarantee: its scrubs repair nothing back."""
    scrub = store.scrub

    def scrub_without_writeback(*a, **kw):
        store._set_page = lambda i, page: None
        try:
            return scrub(*a, **kw)
        finally:
            del store._set_page

    store.scrub = scrub_without_writeback


def read_seed(root: str, spec: dict, cell: dict, seed: int,
              seconds: float) -> dict:
    config = harness.load_config(root, spec, cell["config"])
    mix = traffic.load_mix(BENCH, cell["traffic"])
    mod = harness.load_module("systems", config["system"])
    out = {"seed": seed}
    for role in ("program", "control"):
        system = mod.System(config, mix, seed)
        t0 = time.perf_counter()
        system.setup()
        out[f"{role}_setup_s"] = time.perf_counter() - t0
        if role == "control":
            drop_writeback(system.store)
        window = system.run(seconds)
        out[f"{role}_window_s"] = window["t1"] - window["t0"]
        out[role] = {c["name"]: c["value"] for c in system.verify()}
        del system
        gc.collect()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    spec = harness.load_benchmark(ROOT)
    cell = harness.find_cell(spec, args.workload)
    try:
        harness.require_devices(cell["chips"])
    except harness.NoDevice as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 2
    harness.enable_compile_cache(ROOT)
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(read_seed(ROOT, spec, cell, seed, args.seconds)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
