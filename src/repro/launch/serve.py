"""Batched serving driver with optional NB-LDPC PIM protection.

Prefill the prompt batch, then decode tokens step by step. With
`--protect`, the target projections run through the simulated-PIM +
NB-LDPC path (the paper's deployment scenario); `--fault-rate` injects
the paper's Fig. 6(c) fault model during decode so the ECC actually works.

Usage:
  PYTHONPATH=src python -m repro.launch.serve --arch paper_pim --reduced \
      --batch 4 --prompt-len 16 --gen 8 --protect --fault-rate 1e-3
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.configs.base import PIMSpec
from repro.core.context import PIMContext
from repro.launch.compile_cache import enable_compile_cache
from repro.models import decode_step, init_caches, init_params, prefill


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="paper_pim")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=8)
    ap.add_argument("--protect", action="store_true")
    ap.add_argument("--fault-rate", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced(n_groups=2, d_model=128, n_heads=4, d_ff=256)
    if args.protect and not cfg.pim.enabled:
        cfg = dataclasses.replace(cfg, pim=PIMSpec(
            enabled=True, code_name="wl40_r08", mode="correct", n_iters=4))

    key = jax.random.PRNGKey(args.seed)
    params = init_params(key, cfg)
    B, S = args.batch, args.prompt_len
    prompts = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
    aux = (0.02 * jax.random.normal(key, (B, cfg.n_aux_tokens, cfg.d_model))
           if cfg.aux_kind else None)

    ctx = None
    if args.protect:
        base = PIMContext(cfg.pim)
        ctx = (base.with_faults(jax.random.PRNGKey(7), args.fault_rate)
               if args.fault_rate > 0 else base)

    t0 = time.time()
    logits, caches = prefill(params, cfg, prompts, aux=aux, pim_ctx=ctx)
    # re-home caches into max-length buffers for decoding
    full = init_caches(cfg, B, S + args.gen)

    def place(dst, src):
        if dst.shape == src.shape:
            return src
        pad = [(0, d - s) for d, s in zip(dst.shape, src.shape,
                                          strict=True)]
        return jnp.pad(src, pad)

    caches = jax.tree.map(place, full, caches)
    print(f"prefill: {tuple(logits.shape)} in {time.time()-t0:.2f}s")

    tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
    outs = [tok]
    jdecode = jax.jit(lambda p, c, t, pos: decode_step(p, cfg, c, t, pos,
                                                       pim_ctx=ctx))
    t0 = time.time()
    for i in range(args.gen - 1):
        logits, caches = jdecode(params, caches, tok, jnp.asarray(S + i))
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        outs.append(tok)
    dt = time.time() - t0
    gen = jnp.concatenate(outs, axis=1)
    print("generated tokens:")
    for b in range(B):
        print(f"  [{b}]", np.asarray(gen[b]).tolist())
    print(f"decode: {args.gen-1} steps x {B} seqs in {dt:.2f}s "
          f"({(args.gen-1)*B/max(dt,1e-9):.1f} tok/s)"
          + ("  [NB-LDPC protected]" if args.protect else ""))
    return np.asarray(gen)


if __name__ == "__main__":
    main()
