#!/usr/bin/env python3
"""Smoke run of the protected serving system on one TPU chip.

    python3 chip_smoke.py [--seed N]          # one chip: every phase
    python3 chip_smoke.py --chips 4           # four chips: sharded paths only

Runs the main path once, in this one process, through the public entry
points, and checks each phase against something independent:

- serving: `ServingEngine` over `paper_pim` at its published widths (d_model
  2048, 24 layers, 32/8 heads, head_dim 64, d_ff 8192, vocab 49155; random
  weights from --seed). Four requests of 64-128 prompt tokens and 16 new
  tokens each run clean; the same requests run again with every word of
  every pool page carrying one injected symbol error, and must give the same
  tokens with corrections counted and none uncorrectable. The first decode
  step's logits through the compiled fused attention kernel must match the
  same engine run under the jnp reference policy.
- memory: a `PagedProtectedStore` on `wl1024_r08` (the paper's 1024-symbol
  word) encodes pages on the device (must equal the host encoder), scans
  them after a placed number of errors per word (must equal the exact host
  scan), and repairs every flagged word; the FBP decode through the Pallas
  check-node kernel must equal the jnp decoder bit for bit.
- pim: `repro.launch.serve` at full `paper_pim` width with NB-LDPC-protected
  PIM matmuls, clean and with PIM output faults; both give the same tokens.

With --chips 4 it runs only what exists across chips — `decode_sharded`,
`scan_syndromes_sharded` and the memory controller's automatic sharding —
and requires them to be bit-exact against a one-device run of the same
words.

Progress and numbers go to stdout as JSON lines; the last line is
`{"ok": true, "device": {...}}` only when every check passed. The script
exits non-zero, without that line, when JAX finds no TPU, when the kernel
policy does not resolve to compiled Mosaic kernels, or when any check fails.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

# first decode step: compiled fused attention (fp32 in VMEM) vs the jnp
# reference (bf16 K/V, the unfused path's casts). The two differ by bf16
# roundings of the attention output (2^-8 relative) that the bf16 residual
# stream carries through 24 layers; a wrong kernel is off by O(1).
# max|d logits| / max|logits_ref| must stay under this.
LOGIT_REL_TOL = 5e-2
PROMPT_LENS = (128, 96, 64, 128)
NEW_TOKENS = 16


class SmokeFailure(AssertionError):
    pass


def log(phase: str, **kw) -> None:
    print(json.dumps({"phase": phase, **kw}, default=str), flush=True)


def check(cond, what: str, **detail) -> None:
    if not cond:
        raise SmokeFailure(f"{what}: {detail}")


def memory_mb() -> dict:
    import jax
    st = jax.devices()[0].memory_stats() or {}
    return {k: round(st[k] / 2 ** 20, 1) for k in
            ("bytes_in_use", "peak_bytes_in_use", "bytes_limit") if k in st}


# Pallas entry points of the served kernels. `spy_kernels` wraps each so
# that every trace of it records whether it was built for the interpreter:
# a path that fell back to interpret mode or to the jnp reference (no trace
# at all) cannot pass.
KERNELS = {
    "encode_words": ("repro.kernels.gf_matmul", "gf_matmul_pallas"),
    "scan_syndromes": ("repro.kernels.gf_matmul", "scan_syndromes_pallas"),
    "attend_protected": ("repro.kernels.paged_attention",
                         "attend_protected_pallas"),
    "fbp_cn": ("repro.kernels.fbp", "fbp_cn_pallas"),
}
TRACES: dict[str, list[bool]] = {name: [] for name in KERNELS}


def spy_kernels() -> None:
    import importlib

    from repro.kernels.backend import resolve_interpret

    for name, (mod_name, attr) in KERNELS.items():
        mod = importlib.import_module(mod_name)
        fn = getattr(mod, attr)

        def traced(*a, _fn=fn, _name=name, **kw):
            TRACES[_name].append(resolve_interpret(kw.get("interpret")))
            return _fn(*a, **kw)

        setattr(mod, attr, traced)


def check_kernels(names, require_mosaic: bool) -> None:
    seen = {k: {"traces": len(TRACES[k]), "interpreted": sum(TRACES[k])}
            for k in names}
    log("kernels", **seen)
    for k, v in seen.items():
        check(v["traces"] > 0, f"{k} kernel never ran", **v)
        if require_mosaic:
            check(v["interpreted"] == 0, f"{k} ran in interpret mode", **v)


def mosaic_in(fn, *shapes) -> bool:
    """True when `fn` compiled at `shapes` contains a Mosaic kernel."""
    import jax
    return "tpu_custom_call" in jax.jit(fn).lower(*shapes).compile().as_text()


# ---------------------------------------------------------------------------
# phase: serving
# ---------------------------------------------------------------------------


def serving_phase(cfg, seed: int, *, prompt_lens=PROMPT_LENS,
                  new_tokens: int = NEW_TOKENS, require_mosaic: bool = True):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import get_code
    from repro.kernels import ops, use_policy
    from repro.memory import asymmetric_adjacent
    from repro.memory.paged import words_for_tensor
    from repro.models import init_params
    from repro.models.kv import ProtectedKVConfig
    from repro.serving import ServingEngine

    pkv = ProtectedKVConfig()
    code = get_code(pkv.code_name)
    page_shape = (1, pkv.page_tokens, cfg.n_kv_heads, cfg.head_dim)

    t0 = time.perf_counter()
    params = init_params(jax.random.PRNGKey(seed), cfg)
    jax.block_until_ready(params)
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
    devices = {d for x in jax.tree.leaves(params) for d in x.devices()}
    log("serving.init", params=n_params, init_s=time.perf_counter() - t0,
        devices_used=len(devices), memory_mb=memory_mb())

    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab_size, L) for L in prompt_lens]
    max_seq = max(prompt_lens) + new_tokens + 16

    def engine():
        eng = ServingEngine(params, cfg, max_active=len(prompts),
                            max_seq=max_seq, scrub_every=4,
                            scrub_max_pages=16)
        for i, pr in enumerate(prompts):
            eng.submit(i, pr, new_tokens)
        return eng

    def totals(eng):
        out = {}
        for i in range(len(prompts)):
            for k, v in eng.tenant_stats(i).items():
                out[k] = out.get(k, 0) + v
        return out

    # clean run; keep the first decode step's logits
    t0 = time.perf_counter()
    eng = engine()
    eng.step()
    first_logits = np.asarray(eng.last_logits)
    t_first = time.perf_counter() - t0
    t0 = time.perf_counter()
    clean = eng.run()
    t_rest = time.perf_counter() - t0
    st = totals(eng)
    log("serving.clean", first_step_s=t_first, rest_s=t_rest,
        steps=eng.stats()["step"], pool_pages=eng.pool.capacity_pages,
        pool_mb=eng.pool.capacity_pages * eng.pool.page_words
        * eng.pool.code.n * 4 / 2 ** 20, scrub_rounds=eng.stats()[
            "scrub_rounds"], stats=st, memory_mb=memory_mb())
    check(all(len(t) == new_tokens for t in clean.values()),
          "clean run did not finish every request", tokens=clean)
    check(st["uncorrectable"] == 0, "clean run reported errors", stats=st)
    del eng

    # same requests, one symbol error in every stored word after admission
    t0 = time.perf_counter()
    eng = engine()
    eng.step()
    changed = eng.inject(asymmetric_adjacent(code.p, 1e-3, 1e-3),
                         jax.random.PRNGKey(seed + 1), exact=1)
    faulty = eng.run()
    st = totals(eng)
    log("serving.faulty", s=time.perf_counter() - t0, cells_changed=changed,
        stats=st, scrub_reports=len(eng.scrub_reports),
        memory_mb=memory_mb())
    check(changed > 0, "injection changed no cells")
    check(faulty == clean, "faulty run tokens differ from the clean run",
          clean=clean, faulty=faulty)
    check(st["corrected"] > 0, "no corrections counted", stats=st)
    check(st["uncorrectable"] == 0, "uncorrectable words", stats=st)
    del eng

    # every served kernel ran through its Pallas entry
    check_kernels(("encode_words", "scan_syndromes", "attend_protected"),
                  require_mosaic)

    # compiled fused attention vs the jnp reference on the first step
    t0 = time.perf_counter()
    with use_policy("ref"):
        eng = engine()
        eng.step()
        ref_logits = np.asarray(eng.last_logits)
    del eng
    err = float(np.max(np.abs(first_logits - ref_logits)))
    scale = float(np.max(np.abs(ref_logits)))
    same_argmax = bool((first_logits.argmax(-1)
                        == ref_logits.argmax(-1)).all())
    log("serving.vs_ref", s=time.perf_counter() - t0, max_abs_err=err,
        max_abs_ref=scale, rel_err=err / scale, tol=LOGIT_REL_TOL,
        same_argmax=same_argmax)
    check(np.isfinite(first_logits).all(), "non-finite logits")
    check(err <= LOGIT_REL_TOL * scale, "compiled vs ref logits",
          rel_err=err / scale)

    if require_mosaic:
        W = words_for_tensor(page_shape, code.p, code.k)
        B, NB = len(prompts), 16
        i32, f32, bf16 = jnp.int32, jnp.float32, jnp.bfloat16
        S = jax.ShapeDtypeStruct
        assert code.n * (code.p - 1) ** 2 < 2 ** 31   # int32 accumulator
        ht = jnp.asarray(code.H.T, i32)
        P = jnp.asarray(code.P, i32)
        shapes = [S((NB, B, W, code.n), i32)] * 2 + [S((NB, B), f32)] * 2 \
            + [S((NB, B), i32)]
        hot = S((B,) + page_shape[1:], bf16)
        mosaic = {
            "encode_words": mosaic_in(
                lambda u: ops.encode_words(u, P, code.p), S((W, code.k), i32)),
            "scan_syndromes": mosaic_in(
                lambda y: ops.scan_syndromes(y, ht, code.p),
                S((W, code.n), i32)),
            "attend_protected": mosaic_in(
                lambda q, *a: ops.attend_protected(
                    q, *a, p=code.p, k_info=code.k, page_shape=page_shape),
                S((B, 1, cfg.n_heads, cfg.head_dim), bf16), *shapes, hot, hot,
                S((B,), i32)),
        }
        log("serving.mosaic", tpu_custom_call=mosaic)
        check(all(mosaic.values()), "kernel compiled without Mosaic",
              **mosaic)
    del params
    gc.collect()


# ---------------------------------------------------------------------------
# phase: memory mode at the paper's 1024-symbol word
# ---------------------------------------------------------------------------


def host_flags(code, words):
    import numpy as np
    s = (np.asarray(words, np.int64) @ np.asarray(code.H, np.int64).T) \
        % code.p
    return (s != 0).any(axis=1)


def memory_phase(seed: int, *, code_name: str = "wl1024_r08",
                 page_words: int = 256, n_pages: int = 64, errors: int = 2,
                 require_mosaic: bool = True):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import get_code, np_encode_words
    from repro.core.decode import decode_integers
    from repro.kernels import ops
    from repro.memory import PagedProtectedStore, asymmetric_adjacent

    code = get_code(code_name)
    rng = np.random.default_rng(seed)
    M = page_words * n_pages
    u = rng.integers(0, code.p, (M, code.k))
    ref = np_encode_words(u, code)
    store = PagedProtectedStore(code, page_words=page_words, n_iters=10)
    t0 = time.perf_counter()
    store.append_words(jnp.asarray(u, jnp.int32))
    enc = store.export_words()
    t_enc = time.perf_counter() - t0
    check(np.array_equal(enc, ref), "device encode != host encode",
          mismatched_words=int((enc != ref).any(axis=1).sum()))

    # a placed, correctable error count in every other word
    ch = asymmetric_adjacent(code.p, 1e-3, 1e-3)
    stored = ref.copy()
    stored[::2] = np.asarray(ch.corrupt_exact(
        jax.random.PRNGKey(seed), jnp.asarray(ref[::2], jnp.int32), errors))
    store = PagedProtectedStore(code, page_words=page_words, n_iters=10)
    store.append_encoded(stored)
    t0 = time.perf_counter()
    flags = store.scan_flags()
    t_scan = time.perf_counter() - t0
    want = host_flags(code, stored)
    check(np.array_equal(flags, want), "device scan != host scan",
          device=int(flags.sum()), host=int(want.sum()))
    check(want.sum() == M // 2, "placed errors not all flagged",
          flagged=int(want.sum()))

    t0 = time.perf_counter()
    rep = store.scrub()
    t_scrub = time.perf_counter() - t0
    after = store.export_words()
    log("memory.store", code=code_name, words=M, n=code.n,
        errors_per_word=errors, flagged=int(want.sum()),
        repaired=rep["repaired_words"], encode_s=t_enc, scan_s=t_scan,
        scrub_s=t_scrub, devices_used=len(store.page(0).devices()),
        memory_mb=memory_mb())
    check(np.array_equal(after, ref), "scrub left words unrepaired",
          bad_words=int((after != ref).any(axis=1).sum()))

    # FBP through the Pallas check-node kernel vs the jnp decoder
    y = jnp.asarray(stored[want][:2048], jnp.int32)
    kw = dict(n_iters=10, damping=0.3, early_exit=True)
    dec_kernel = jax.jit(lambda y: decode_integers(
        code, y, cn_fbp=ops.fbp_cn_batched, **kw))
    dec_jnp = jax.jit(lambda y: decode_integers(code, y, **kw))
    t0 = time.perf_counter()
    yk, rk = dec_kernel(y)
    yk = np.asarray(yk)
    t_k = time.perf_counter() - t0
    yj, rj = dec_jnp(y)
    yj = np.asarray(yj)
    log("memory.fbp", words=int(y.shape[0]), dc=int(code.dc_max),
        kernel_s=t_k, fails=int(np.asarray(rk.detect_fail).sum()))
    check_kernels(("fbp_cn",), require_mosaic)
    check(np.array_equal(yk, yj), "fbp_cn kernel decode != jnp decode",
          mismatched_words=int((yk != yj).any(axis=1).sum()))
    check(np.array_equal(np.asarray(rk.iterations),
                         np.asarray(rj.iterations)),
          "fbp_cn kernel iteration counts != jnp decoder")
    check(np.array_equal(yk, ref[want][:2048]), "decoded words != codewords")
    if require_mosaic:
        ok = mosaic_in(lambda m: ops.fbp_cn(m, code.p),
                       jax.ShapeDtypeStruct((2048 * code.c, code.dc_max,
                                             code.p), jnp.float32))
        log("memory.mosaic", tpu_custom_call={"fbp_cn": ok})
        check(ok, "fbp_cn compiled without Mosaic")


# ---------------------------------------------------------------------------
# phase: PIM mode through the serving launcher
# ---------------------------------------------------------------------------


def pim_phase(seed: int, argv=("--arch", "paper_pim", "--batch", "4",
                               "--prompt-len", "64", "--gen", "4")):
    import numpy as np

    from repro.launch import serve

    base = list(argv) + ["--protect", "--seed", str(seed)]
    t0 = time.perf_counter()
    clean = np.asarray(serve.main(base))
    t_clean = time.perf_counter() - t0
    gc.collect()
    t0 = time.perf_counter()
    faulty = np.asarray(serve.main(base + ["--fault-rate", "1e-3"]))
    gc.collect()
    log("pim.serve", clean_s=t_clean, faulty_s=time.perf_counter() - t0,
        tokens=clean.tolist(), faulty_tokens=faulty.tolist(),
        memory_mb=memory_mb())
    check(np.array_equal(clean, faulty),
          "PIM faulty run tokens differ from the clean run")


# ---------------------------------------------------------------------------
# phase: sharded paths over every chip (--chips 4)
# ---------------------------------------------------------------------------


def sharded_phase(seed: int, *, code_name: str = "wl1024_r08",
                  words: int = 4096, errors: int = 2):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import get_code, np_encode_words
    from repro.core.decode import decode_integers
    from repro.distributed.sharding import (data_mesh, decode_sharded,
                                            scan_syndromes_sharded)
    from repro.kernels import ops
    from repro.memory import ProtectedMemoryArray, asymmetric_adjacent

    ndev = len(jax.devices())
    code = get_code(code_name)
    rng = np.random.default_rng(seed)
    cw = np_encode_words(rng.integers(0, code.p, (words, code.k)), code)
    ch = asymmetric_adjacent(code.p, 1e-3, 1e-3)
    y = np.asarray(cw).copy()
    y[::2] = np.asarray(ch.corrupt_exact(jax.random.PRNGKey(seed),
                                         jnp.asarray(cw[::2], jnp.int32),
                                         errors))
    mesh = data_mesh()
    check(mesh.devices.size == ndev, "mesh does not span every device",
          mesh=mesh.devices.size, devices=ndev)
    kw = dict(n_iters=10, damping=0.3, early_exit=True)
    yd = jnp.asarray(y, jnp.int32)
    one = jax.devices()[0]
    ht = jax.device_put(jnp.asarray(code.H.T, jnp.int32), one)

    assert code.n * (code.p - 1) ** 2 < 2 ** 31      # int32 accumulator
    scan_sh = jax.jit(lambda y: scan_syndromes_sharded(code, y, mesh=mesh))
    dec_sh = jax.jit(lambda y: decode_sharded(code, y, mesh=mesh, **kw))
    scan_1 = jax.jit(lambda y: ops.scan_syndromes(y, ht, code.p))
    dec_1 = jax.jit(lambda y: decode_integers(code, y, **kw))
    t0 = time.perf_counter()
    flags_sh = scan_sh(yd)
    ycorr_sh, res_sh = dec_sh(yd)
    jax.block_until_ready((flags_sh, ycorr_sh))
    t_sh = time.perf_counter() - t0
    spread = {"scan": len(flags_sh.sharding.device_set),
              "decode": len(ycorr_sh.sharding.device_set),
              "decode_shards": len({s.device for s in
                                    ycorr_sh.addressable_shards})}
    check(min(spread.values()) == ndev, "sharded outputs not spread over "
          "every device", **spread)

    y1 = jax.device_put(yd, one)
    t0 = time.perf_counter()
    flags_1 = scan_1(y1)
    ycorr_1, res_1 = dec_1(y1)
    jax.block_until_ready((flags_1, ycorr_1))
    t_1 = time.perf_counter() - t0
    check(len(ycorr_1.sharding.device_set) == 1, "reference left one device")
    flags_sh, flags_1 = np.asarray(flags_sh), np.asarray(flags_1)
    check(np.array_equal(flags_sh, flags_1), "sharded scan != one-device",
          sharded=int(flags_sh.sum()), one=int(flags_1.sum()))
    check(np.array_equal(flags_1, host_flags(code, y)),
          "one-device scan != host scan")
    check(np.array_equal(np.asarray(ycorr_sh), np.asarray(ycorr_1)),
          "sharded decode != one-device decode")
    check(np.array_equal(np.asarray(res_sh.iterations),
                         np.asarray(res_1.iterations)),
          "sharded iteration counts != one-device")
    log("sharded.kernels", devices_used=spread, words=words, n=code.n,
        flagged=int(flags_1.sum()), sharded_s=t_sh, one_device_s=t_1,
        decoded_equal_codewords=bool(np.array_equal(np.asarray(ycorr_1),
                                                     cw)))

    # the controller shards on its own when it sees several devices
    data = rng.normal(size=(512, 256)).astype(np.float32)
    out = {}
    for name, sharded in (("auto", None), ("one_device", False)):
        mem = ProtectedMemoryArray(code, controller="basic",
                                   use_sharded=sharded)
        mem.write("w", data)
        mem.inject(ch, key=jax.random.PRNGKey(seed + 7), exact=errors)
        t0 = time.perf_counter()
        rep = mem.scrub()
        read = mem.read("w")
        out[name] = dict(use_sharded=mem.controller.use_sharded,
                         scrub=rep, read=read, enc=mem.stored("w").enc.copy(),
                         s=time.perf_counter() - t0)
    a, b = out["auto"], out["one_device"]
    keys = ("backend", "flagged", "corrected", "uncorrectable")
    log("sharded.controller", auto_sharded=a["use_sharded"],
        auto={k: a["scrub"][k] for k in keys},
        one_device={k: b["scrub"][k] for k in keys},
        auto_s=a["s"], one_device_s=b["s"])
    check(a["use_sharded"] is True and b["use_sharded"] is False,
          "controller did not pick sharding from the device count")
    check(all(a["scrub"][k] == b["scrub"][k] for k in keys),
          "auto-sharded scrub report != one-device report")
    check(a["scrub"]["flagged"] > 0 and a["scrub"]["uncorrectable"] == 0,
          "scrub flagged nothing or left words uncorrectable")
    check(np.array_equal(a["enc"], b["enc"]),
          "auto-sharded scrub storage != one-device scrub storage")
    check(np.array_equal(a["read"], b["read"]) and np.array_equal(
        a["read"], data), "auto-sharded read != one-device read")


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX platform {devs[0].platform!r})",
              file=sys.stderr)
        return 2
    if len(devs) != args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devs)} devices", file=sys.stderr)
        return 2

    from repro.configs import get_config
    from repro.kernels import current_policy
    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    mode = current_policy().resolve()
    log("start", platform=devs[0].platform, kind=devs[0].device_kind,
        count=len(devs), kernel_policy=mode, compile_cache=cache,
        jax=jax.__version__)
    if mode != "compiled":
        print(f"chip_smoke: kernel policy resolved to {mode!r}, not "
              "'compiled'", file=sys.stderr)
        return 2

    spy_kernels()
    t0 = time.perf_counter()
    if args.chips == 4:
        sharded_phase(args.seed)
    else:
        serving_phase(get_config("paper_pim"), args.seed)
        memory_phase(args.seed)
        pim_phase(args.seed)
    log("done", total_s=time.perf_counter() - t0, memory_mb=memory_mb())
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
