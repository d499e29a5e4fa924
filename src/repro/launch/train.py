"""End-to-end training driver.

Production shape: config-driven model, shard-aware resumable data pipeline,
AdamW/Adafactor, atomic checkpoints + RestartManager (crash-resilient),
straggler watchdog, logical-axis sharding on whatever mesh is available.

On this CPU container it trains reduced configs for real (the 100M-scale
end-to-end example); on TPU pods the same driver lowers the full configs —
nothing here is CPU-specific.

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch granite_3_2b \
      --reduced --steps 120 --batch 8 --seq 128 --ckpt-dir /tmp/run1
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import SHAPES, get_config
from repro.configs.base import ShapeSpec
from repro.data import DataConfig, TokenPipeline
from repro.distributed.fault import RestartManager, StragglerWatchdog
from repro.distributed.sharding import use_rules
from repro.launch.cells import rules_for_cell, settings_for
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.launch.steps import build_train
from repro.models import init_params


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite_3_2b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--d-model", type=int, default=256)
    ap.add_argument("--n-groups", type=int, default=4)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--vocab", type=int, default=512)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train")
    ap.add_argument("--save-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced(n_groups=args.n_groups, d_model=args.d_model,
                          n_heads=max(4, args.d_model // 64),
                          d_ff=4 * args.d_model, vocab=args.vocab)
    shape = ShapeSpec("custom", args.seq, args.batch, "train")
    st = dataclasses.replace(settings_for(args.arch, shape),
                             microbatches=args.microbatches)

    mesh = make_host_mesh(data=len(jax.devices()))
    rules = rules_for_cell(mesh, cfg, shape, st)

    train_step, _specs, shardings, tx = build_train(
        cfg, st, shape, lr=args.lr, total_steps=args.steps)
    in_sh, out_sh = shardings(mesh, rules)

    dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                      global_batch=args.batch, seed=args.seed)
    mgr = RestartManager(args.ckpt_dir, save_every=args.save_every)
    dog = StragglerWatchdog()

    def init_state():
        params = init_params(jax.random.PRNGKey(args.seed), cfg)
        return {"params": params, "opt": tx.init(params)}

    state, start_step, data_state = mgr.restore_or_init(init_state)
    pipe = (TokenPipeline.restore(dcfg, data_state) if data_state
            else TokenPipeline(dcfg, step=start_step))

    jstep = jax.jit(train_step, in_shardings=in_sh, out_shardings=out_sh,
                    donate_argnums=(0, 1))

    params, opt = state["params"], state["opt"]
    aux = None
    if cfg.aux_kind:
        aux = 0.02 * np.random.default_rng(0).standard_normal(
            (args.batch, cfg.n_aux_tokens, cfg.d_model)).astype(np.float32)

    losses = []
    with use_rules(mesh, rules):
        for step in range(start_step, args.steps):
            dog.step_start()
            batch = next(pipe)
            batch = {k: jnp.asarray(v) for k, v in batch.items()}
            if aux is not None:
                batch["aux"] = jnp.asarray(aux)
            params, opt, metrics = jstep(params, opt, batch)
            dt = dog.step_end(step)
            loss = float(metrics["loss"])
            losses.append(loss)
            if step % args.log_every == 0 or step == args.steps - 1:
                print(f"step {step:5d}  loss {loss:.4f}  "
                      f"gnorm {float(metrics['grad_norm']):.3f}  {dt:.2f}s",
                      flush=True)
            mgr.maybe_save(step, {"params": params, "opt": opt},
                           data_state=pipe.state())

    print(f"final loss {losses[-1]:.4f} (first {losses[0]:.4f}); "
          f"stragglers flagged: {len(dog.flagged)}")
    return losses


if __name__ == "__main__":
    main()
