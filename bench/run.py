#!/usr/bin/env python3
"""Run one benchmark cell on the chip this process holds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name from `BENCHMARK.json` at the checkout root:
the cell's configuration file (`configs`), its traffic mix
(`bench/traffic/<traffic>.json`), the system driver the configuration
names (`bench/systems/<system>.py`), the end-to-end metrics
(`bench/e2e/<name>.py`) and the per-layer metrics
(`bench/metrics/<name>.py`). A new cell, mix, configuration or metric is
a new file and an entry, never an edit.

The run: check the devices (a TPU, as many chips as the cell asks for);
set up the system from `--seed` and warm every shape the cell uses
(`setup_s`, from process start); measure for `--seconds`; read the device
memory peak; free the system's state and compare what the window produced
with the plain reference (`correct`). With `--trace 1` the window runs
under the JAX profiler and the program's spans, and the per-layer metrics
are reported instead of the end-to-end ones.

The last lines on stderr, and the `checks` key that ends the result line,
give every number compared beside its limit. The result is the last line
of stdout. No result is printed, and the exit code is not 0, when JAX
finds no TPU or too few chips, or when a served kernel ran interpreted.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))

from lib import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    spec = harness.load_benchmark(ROOT)
    cell = harness.find_cell(spec, args.workload)
    try:
        device = harness.require_devices(cell["chips"])
    except harness.NoDevice as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    harness.enable_compile_cache(ROOT)
    result = harness.run_cell(ROOT, spec, cell, seed=args.seed,
                              seconds=args.seconds, trace=bool(args.trace),
                              device=device, t_start=T_START)
    if result is None:
        return 3
    harness.print_checks(result["checks"])
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
