"""Benchmark harness: one module per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [--quick] [--only NAME]

Prints one CSV-ish line per measurement and a per-bench validation summary
(EXPERIMENTS.md mirrors these numbers)."""
from __future__ import annotations

import argparse
import os
import time

BENCHES = [
    ("wordlen_fig6a", "benchmarks.bench_wordlen"),
    ("coderate_fig6b", "benchmarks.bench_coderate"),
    ("dnn_fig6c", "benchmarks.bench_dnn_recovery"),
    ("table2_efficiency", "benchmarks.bench_ecc_efficiency"),
    ("decoder_throughput_fig5", "benchmarks.bench_decoder_throughput"),
    ("memory_mode", "benchmarks.bench_memory_mode"),
    ("scrub_engine", "benchmarks.bench_scrub"),
    ("kv_serving", "benchmarks.bench_kv_serving"),
    ("multitenant", "benchmarks.bench_multitenant"),
    ("dse_fig7", "benchmarks.bench_dse"),
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None)
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    all_rows = {}
    for name, mod_name in BENCHES:
        if args.only and args.only not in name:
            continue
        mod = __import__(mod_name, fromlist=["main"])
        t0 = time.time()
        try:
            rows = mod.main(quick=args.quick)
        except Exception as e:                           # noqa: BLE001
            print(f"[{name}] FAILED: {type(e).__name__}: {e}", flush=True)
            continue
        dt = time.time() - t0
        all_rows[name] = rows
        print(f"\n=== {name} ({dt:.1f}s) ===", flush=True)
        for r in rows:
            print(",".join(f"{k}={v:.4g}" if isinstance(v, float)
                           else f"{k}={v}" for k, v in r.items()), flush=True)

    # headline validations
    print("\n=== validation summary ===")
    wl = all_rows.get("wordlen_fig6a", [])
    big = [r for r in wl if r.get("n") == 1024 and r.get("raw_ber") == 1e-5]
    if big:
        post = float(big[0]["post_ber"])
        # conditional-MC resolution: one residual symbol in (trials x n)
        floor = 1.0 / (96 * 1024) * 0.05   # ~ pmf-weighted floor at 1e-5
        if post <= floor:
            print(f"wl1024 @ raw 1e-5: post < {floor:.1e} (no residual "
                  f"errors in any conditional trial) => improvement >= "
                  f"{1e-5 / floor:.0f}x — consistent with the paper's "
                  f"59.65x to 1.676e-7, below our measurement floor")
        else:
            print(f"wl1024 @ raw 1e-5: post={post:.3g} "
                  f"improvement={1e-5 / post:.1f}x "
                  f"(paper: 59.65x to 1.676e-7)")
    t2 = all_rows.get("table2_efficiency", [])
    ours = [r for r in t2 if r.get("design") == "this_work_nbldpc"]
    if ours:
        print(f"ECC efficiency: {ours[0]['eff_mbps_w']} Mbps/W, "
              f"{ours[0]['improvement_vs_best']}x best prior "
              f"(paper: 1152.00, 2.978x); MTE={ours[0]['mte_measured']} "
              f"(paper: 5 @ wl256)")
    mm = all_rows.get("memory_mode", [])
    acc = [r for r in mm if r.get("section") == "acceptance"]
    if acc:
        a = acc[0]
        print(f"memory mode @ raw {a['raw_ber']:.0e} (Hamming SECDED "
              f"saturated at {a['hamming_improvement']:.2f}x): NB-LDPC "
              f"improvement {a['nbldpc_improvement']:.1f}x over unprotected "
              f"(acceptance: >= 10x, pass={a['pass']})")
    kv = all_rows.get("kv_serving", [])
    kacc = [r for r in kv if r.get("section") == "acceptance"]
    if kacc:
        a = kacc[0]
        print(f"protected KV serving [{a['code']}]: slowdown "
              f"{a['protected_slowdown']}x vs same-driver dense, overlap "
              f"{a['overlap_speedup']}x vs sync whole-cache decode, ppl "
              f"delta {a['ppl_delta_protected']} protected vs "
              f"{a['ppl_delta_unprotected']} unprotected @ raw 1e-2 "
              f"(pass={a['pass']})")
    mt = all_rows.get("multitenant", [])
    macc = [r for r in mt if r.get("section") == "acceptance"]
    if macc:
        a = macc[0]
        print(f"multi-tenant serving [{a['code']}]: aggregate "
              f"{a['protected_tps_1']} -> {a['protected_tps_16']} tok/s "
              f"(1 -> 16 tenants, {a['scaling_1_to_16']}x, acceptance >= "
              f"2x), bit_exact={a['bit_exact']}, concurrent scrub cost "
              f"{a['scrub_cost_frac'] * 100:.1f}% (acceptance < 20%), "
              f"pass={a['pass']}")
    os.makedirs("results", exist_ok=True)
    from .rows import append_rows
    for name, rows in all_rows.items():
        append_rows("results/bench_rows.json", name, rows)


if __name__ == "__main__":
    main()
