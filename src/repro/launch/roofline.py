"""Roofline analysis over the dry-run artifacts (§Roofline).

Per (arch x shape) on the single-pod mesh:
    compute term    = HLO_FLOPs_per_dev / peak_FLOP/s
    memory term     = HLO_bytes_per_dev / HBM_bw
    collective term = collective_wire_bytes_per_dev / ICI_link_bw
(Terms are seconds-per-step; the largest term is the bottleneck. HLO counts
come from the unrolled cost lowerings — see dryrun.py.)

Also reports MODEL_FLOPS = 6·N·D (train) / 2·N·D (inference), N_active for
MoE, and the MODEL/HLO ratio (useful-compute fraction; remat and the
replicated-head inefficiency show up here).

Usage:  python -m repro.launch.roofline [--mesh single] [--csv out.csv]
"""
from __future__ import annotations

import argparse
import glob
import json
import os

import numpy as np

# Per-chip peaks keyed by `jax.devices()[0].device_kind`. TPU v5e: Google
# Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 819 GB/s HBM); the
# per-link ICI figure is the dry-run's planning number. A kind that is not
# in the table is an error, never a default.
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bw": 819e9, "ici_bw": 50e9},
}
# the dry-run placeholder meshes model TPU v5e pods
DRYRUN_DEVICE_KIND = "TPU v5 lite"


def peaks(device_kind: str) -> dict:
    """Peak bf16 FLOP/s, HBM bytes/s and ICI bytes/s per link of one chip."""
    if device_kind not in PEAKS:
        raise KeyError(f"no peak table entry for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}")
    return PEAKS[device_kind]

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "results", "dryrun")


def count_params(cfg):
    """(total params, active params) from shapes (no allocation)."""
    import jax
    from repro.models import init_params

    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))

    def sizes(tree):
        return sum(np.prod(l.shape) for l in jax.tree.leaves(tree))

    total = sizes(shapes)
    active = total
    if cfg.n_experts:
        moe = 0
        for pos in shapes["groups"].values():
            if "moe" in pos:
                e = {k: v for k, v in pos["moe"].items() if k != "router"}
                moe += sizes(e)
        active = total - moe + moe * cfg.top_k / cfg.n_experts
    return float(total), float(active)


def model_flops(cfg, shape, n_active: float) -> float:
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode"
                                   else 1)
    mult = 6.0 if shape.kind == "train" else 2.0
    return mult * n_active * tokens


def analyze_cell(path: str):
    pk = peaks(DRYRUN_DEVICE_KIND)
    with open(path) as f:
        d = json.load(f)
    if d.get("skipped") or not d.get("ok") or "composed" not in d:
        return d, None
    from repro.configs import SHAPES, get_config
    cfg = get_config(d["arch"])
    shape = SHAPES[d["shape"]]
    nd = 512 if d["mesh"] == "multi" else 256

    comp = d["composed"]
    t_compute = comp["flops_per_dev"] / pk["flops"]
    t_memory = comp["bytes_per_dev"] / pk["hbm_bw"]
    t_coll = comp["collective_wire_bytes"] / pk["ici_bw"]
    terms = {"compute": t_compute, "memory": t_memory, "collective": t_coll}
    bottleneck = max(terms, key=terms.get)

    total, active = count_params(cfg)
    mf = model_flops(cfg, shape, active)
    hlo_global = comp["flops_per_dev"] * nd
    ratio = mf / hlo_global if hlo_global else 0.0

    # roofline fraction: useful model flops per second at the bottleneck
    step_time = max(terms.values())
    mfu = mf / nd / step_time / pk["flops"] if step_time else 0.0

    mem = d["full"]["mem"]
    hbm_gb = (mem["argument_bytes"] + mem["temp_bytes"]
              + mem["output_bytes"] - mem["alias_bytes"]) / 2**30

    row = {
        "arch": d["arch"], "shape": d["shape"], "mesh": d["mesh"],
        "params_B": total / 1e9, "active_B": active / 1e9,
        "t_compute_s": t_compute, "t_memory_s": t_memory,
        "t_collective_s": t_coll, "bottleneck": bottleneck,
        "model_flops": mf, "hlo_flops_global": hlo_global,
        "useful_ratio": ratio, "roofline_mfu": mfu,
        "hbm_gb_per_dev": hbm_gb,
    }
    return d, row


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", default="single")
    ap.add_argument("--csv", default=None)
    ap.add_argument("--variants", action="store_true",
                    help="include optimized-variant artifacts (__flash etc.)")
    args = ap.parse_args()

    rows, skips, fails = [], [], []
    for path in sorted(glob.glob(os.path.join(RESULTS_DIR, "*.json"))):
        name = os.path.basename(path)[:-5]
        is_variant = name.count("__") > 2
        if is_variant != args.variants:
            continue
        with open(path) as f:
            d = json.load(f)
        if d["mesh"] != args.mesh:
            continue
        if d.get("skipped"):
            skips.append((d["arch"], d["shape"], d["reason"]))
            continue
        if not d.get("ok"):
            fails.append((d["arch"], d["shape"], d.get("error", "?")[:100]))
            continue
        _, row = analyze_cell(path)
        if row:
            if is_variant:
                tail = name.split("__", 3)[-1]
                row["arch"] = f"{d['arch']}+{tail}"
            rows.append(row)

    hdr = (f"{'arch':24s} {'shape':12s} {'prm(B)':>7s} {'t_comp':>9s} "
           f"{'t_mem':>9s} {'t_coll':>9s} {'bound':>10s} {'useful':>7s} "
           f"{'MFU':>6s} {'HBM(GiB)':>8s}")
    print(hdr)
    print("-" * len(hdr))
    for r in sorted(rows, key=lambda r: (r["arch"], r["shape"])):
        print(f"{r['arch'][:24]:24s} {r['shape']:12s} {r['params_B']:7.1f} "
              f"{r['t_compute_s']:9.4f} {r['t_memory_s']:9.4f} "
              f"{r['t_collective_s']:9.4f} {r['bottleneck']:>10s} "
              f"{r['useful_ratio']:7.3f} {r['roofline_mfu']:6.3f} "
              f"{r['hbm_gb_per_dev']:8.2f}")
    for a, s, reason in skips:
        print(f"{a:24s} {s:12s} SKIP: {reason[:80]}")
    for a, s, e in fails:
        print(f"{a:24s} {s:12s} FAIL: {e}")

    if args.csv:
        import csv
        with open(args.csv, "w", newline="") as f:
            w = csv.DictWriter(f, fieldnames=list(rows[0].keys()))
            w.writeheader()
            w.writerows(rows)
        print(f"wrote {args.csv}")


if __name__ == "__main__":
    main()
