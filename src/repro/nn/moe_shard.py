"""Expert-parallel MoE via shard_map: explicit all-to-all dispatch.

The pjit-native sort/scatter formulation (moe.py::moe_sorted_ep) is correct
but GSPMD lowers its cross-shard scatter to full-buffer all-reduces — the
dry-run measured 9 x 8 GiB all-reduces per layer on olmoe (train_4k), making
every MoE cell collective-bound. This module is the production path:

  1. shard_map over (data..., model): each data shard routes its LOCAL tokens
     (router weights are replicated);
  2. tokens are packed locally into per-expert capacity buckets
     (E, cap_local, d) — a *local* scatter, no collective;
  3. ONE all_to_all over the `model` (expert-parallel) axis moves each bucket
     to its expert's shard — wire bytes = the tokens actually routed
     (top_k copies of each token), the information-theoretic minimum;
  4. grouped GEMM over the local experts' received buckets;
  5. the reverse all_to_all returns expert outputs; a local gather+weighted
     combine finishes.

Differentiable end to end (all_to_all and the local scatters have exact
transposes), so the same path serves training and inference.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.distributed.sharding import active_mesh
from .layers import ACTS, CDT


def _local_moe(x, router, w_gate, w_up, w_down, cfg: ArchConfig, ep: int):
    """Per-shard body. x: (T_loc, d) local tokens; experts sharded: w_*
    carry E_loc = E/ep experts. Runs under shard_map with axis 'model'."""
    act = ACTS[cfg.act]
    T, d = x.shape
    E, K = cfg.n_experts, cfg.top_k
    E_loc = w_gate.shape[0]
    cap = max(1, int(T * K / E * cfg.capacity_factor))

    # ---- route locally (router replicated) --------------------------------
    logits = (x @ router.astype(CDT)).astype(jnp.float32)      # (T, E)
    topv, topi = jax.lax.top_k(logits, K)
    gate = jax.nn.softmax(topv, axis=-1).astype(CDT)           # (T, K)

    # ---- pack into per-(global)expert capacity buckets (local scatter) ----
    flat_e = topi.reshape(-1)                                  # (T*K,)
    order = jnp.argsort(flat_e)
    sorted_e = flat_e[order]
    tok_of = order // K
    counts = jnp.bincount(flat_e, length=E)
    starts = jnp.cumsum(counts) - counts
    pos_in_e = jnp.arange(T * K, dtype=jnp.int32) - starts[sorted_e]
    keep = pos_in_e < cap
    safe_pos = jnp.where(keep, pos_in_e, cap)

    send = jnp.zeros((E, cap + 1, d), CDT)
    send = send.at[sorted_e, safe_pos].set(x[tok_of])          # local only
    send = send[:, :cap]

    # ---- all_to_all over the expert-parallel axis -------------------------
    # (E, cap, d) -> split E across `ep` shards, concat the received shards:
    # recv: (E_loc * ep, cap, d) = every shard's buckets for MY experts.
    recv = jax.lax.all_to_all(send.reshape(ep, E_loc, cap, d), "model",
                              split_axis=0, concat_axis=0, tiled=False)
    recv = recv.reshape(ep, E_loc, cap, d).transpose(1, 0, 2, 3)
    recv = recv.reshape(E_loc, ep * cap, d)                    # per local exp.

    # ---- grouped GEMM over local experts -----------------------------------
    g = jnp.einsum("ecd,edf->ecf", recv, w_gate.astype(CDT))
    u = jnp.einsum("ecd,edf->ecf", recv, w_up.astype(CDT))
    h = act(g) * u
    y = jnp.einsum("ecf,efd->ecd", h, w_down.astype(CDT))      # (E_loc,ep*cap,d)

    # ---- return tokens to their source shards ------------------------------
    y = y.reshape(E_loc, ep, cap, d).transpose(1, 0, 2, 3)     # (ep,E_loc,...)
    back = jax.lax.all_to_all(y, "model", split_axis=0, concat_axis=0,
                              tiled=False)
    back = back.reshape(E, cap, d)                             # my tokens' outs

    # ---- local unscatter + weighted combine --------------------------------
    pad = jnp.zeros((E, 1, d), CDT)
    backp = jnp.concatenate([back, pad], axis=1)               # row `cap`=0
    y_slots = backp[sorted_e, safe_pos]                        # (T*K, d)
    y_slots = jnp.where(keep[:, None], y_slots, 0)
    gate_slots = gate.reshape(-1)[order]
    out = jnp.zeros((T, d), CDT).at[tok_of].add(
        gate_slots[:, None] * y_slots)
    return out


def moe_shard_apply(params, x, cfg: ArchConfig):
    """x: (B, S, d). Requires an active mesh with a `model` axis (EP);
    falls back to the pjit path without one (unit tests, 1-device)."""
    mesh = active_mesh()
    if mesh is None or "model" not in mesh.axis_names:
        from .moe import moe_apply
        return moe_apply(params, x, cfg)
    ep = mesh.shape["model"]
    B, S, d = x.shape
    dp_axes = tuple(a for a in mesh.axis_names if a != "model")

    fn = functools.partial(_local_moe, cfg=cfg, ep=ep)
    # check_vma=False: the expert body's all_to_all pattern is rejected by
    # the static replication checker
    mapped = jax.shard_map(
        fn, mesh=mesh, check_vma=False,
        in_specs=(P(dp_axes, None),                # x2d: tokens over DP axes
                  P(),                             # router replicated
                  P("model", None, None),          # experts over model
                  P("model", None, None),
                  P("model", None, None)),
        out_specs=P(dp_axes, None),
    )
    y = mapped(x.reshape(B * S, d).astype(CDT), params["router"],
               params["w_gate"], params["w_up"], params["w_down"])
    return y.reshape(B, S, d)
