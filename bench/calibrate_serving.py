#!/usr/bin/env python3
"""Readings that the serving cell's limits are set from (not run by the
benchmark's own runs).

    python3 bench/calibrate_serving.py --workload granite.batch-longdoc \\
        --seeds 1,2,3 --seconds 51 [--controls fp8,int4] [--fault fp8]

For each seed, in one process: set the cell up, run its window at the
cell's own load for `--seconds`, read the device memory peak and every
number that `verify` compares for the sound run (`program`), then, on
the same served requests, what `verify(control=...)` compares for each
control: the reference put in the program's place, at the configuration's bfloat16
matmuls and with its KV pages in another precision (`int4`, the one
below the configuration's int8; `fp8`: e4m3 scaled to the page's
absmax). `--fault fp8` plants a fault in the
program instead: every K/V row the engine stores is rounded to fp8 e4m3
at its absmax first, and only that run's numbers are read.

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


def plant_fp8() -> None:
    """Round every K/V row the engine stores (hot rows and prompt pages)
    to fp8 e4m3 at its absmax."""
    import jax.numpy as jnp
    from repro.serving import engine

    def fp8(x):
        xf = x.astype(jnp.float32)
        s = jnp.maximum(jnp.max(jnp.abs(xf)), 1e-30) / 448.0
        return ((xf / s).astype(jnp.float8_e4m3fn).astype(jnp.float32)
                * s).astype(x.dtype)

    kv = engine.BatchedPagedKV
    append, ingest = kv.append, kv.ingest_slot
    kv.append = lambda self, k, v: append(self, fp8(k), fp8(v))
    kv.ingest_slot = lambda self, b, k, v: ingest(self, b, fp8(k), fp8(v))


def read_seed(root: str, spec: dict, cell: dict, seed: int, seconds: float,
              controls: list[str]) -> dict:
    config = harness.load_config(root, spec, cell["config"])
    mix = traffic.load_mix(BENCH, cell["traffic"])
    mod = harness.load_module("systems", config["system"])
    system = mod.System(config, mix, seed)
    t0 = time.perf_counter()
    system.setup()
    t1 = time.perf_counter()
    window = system.run(seconds)
    peak = harness.memory_peak_bytes(cell["chips"])
    t2 = time.perf_counter()
    checks = {c["name"]: c["value"] for c in system.verify()}
    t3 = time.perf_counter()
    out = {"seed": seed, "setup_s": t1 - t0,
           "window_s": window["t1"] - window["t0"],
           "steps": len(window["steps"]), "tokens": window["tokens"],
           "admitted": sum(s["admitted"] for s in window["steps"]),
           "retired": sum(s["retired"] for s in window["steps"]),
           "memory_peak_bytes": peak, "verify_s": t3 - t2,
           "program": checks}
    for kv in controls:
        out[f"control_{kv}"] = {c["name"]: c["value"]
                                for c in system.verify(control=kv)}
    out["controls_s"] = time.perf_counter() - t3
    del system
    gc.collect()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--controls", default="fp8,int4")
    ap.add_argument("--fault", choices=("fp8",))
    args = ap.parse_args(argv)
    spec = harness.load_benchmark(ROOT)
    cell = harness.find_cell(spec, args.workload)
    try:
        harness.require_devices(cell["chips"])
    except harness.NoDevice as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 2
    harness.enable_compile_cache(ROOT)
    controls = [c for c in args.controls.split(",") if c]
    if args.fault:
        plant_fp8()
        controls = []
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(read_seed(ROOT, spec, cell, seed, args.seconds,
                                   controls)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
