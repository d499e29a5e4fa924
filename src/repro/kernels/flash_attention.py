"""Pallas TPU flash attention (fwd + bwd) — the framework's attention
hot-spot kernel.

Why it exists here: the dry-run baselines show the memory roofline term of
nearly every (arch x shape) cell is dominated by the O(Sq*Skv) attention
intermediates (logits/softmax/probability tensors hitting HBM). The flash
formulation keeps them VMEM-resident: HBM traffic becomes O(S*D) for
q/k/v/o (+ the (B,H,S) logsumexp), which is what the §Perf iterations claim
for the memory term.

Layout: heads are folded into the leading grid axis. q: (BH, Sq, D);
k/v: (BHkv, Skv, D); GQA maps grid row b -> kv row via b//G computed inside
the index_map. Grid = (BH, nq, nkv) with the KV axis innermost; the output
block (and the fp32 m/l/acc running state in VMEM scratch) is revisited
across the KV steps — the standard online-softmax recurrence:

    m' = max(m, rowmax(s));  c = exp(m - m')
    l' = l*c + rowsum(exp(s - m'));  acc' = acc*c + exp(s - m') @ v

Masking (causal / sliding-window) is computed from global indices via iota,
so padded tails and ring-buffer decode windows need no mask tensors in HBM.
Soft-capping (gemma2) is applied to the raw scores in both fwd and bwd
(derivative recomputed from the capped value: d tanh = 1 - tanh^2).

VMEM working set per grid step (bq = BLOCK_Q = 512, bk = BLOCK_KV = 512,
D = 128, fp32 scratch): q 512x128x2B + k/v 2x512x128x2B + s/p 512x512x4B +
acc 512x128x4B + m/l 2x512x4B ~= 1.6 MiB — comfortably inside a v5e core's
VMEM with double-buffering headroom.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .backend import resolve_interpret

DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_KV = 512
NEG_INF = -1e30


def _mask(qpos, kpos, causal: bool, window: int, kv_len: int = 0):
    ok = jnp.ones(qpos.shape[:1] + kpos.shape[:1], jnp.bool_)
    if causal:
        ok &= kpos[None, :] <= qpos[:, None]
    if window:
        ok &= kpos[None, :] > qpos[:, None] - window
    if kv_len:                       # padded KV tail (non-block-aligned Skv)
        ok &= kpos[None, :] < kv_len
    return ok


def _scores(q, k, scale, softcap):
    s = jax.lax.dot_general(q.astype(jnp.float32), k.astype(jnp.float32),
                            (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if softcap:
        s = softcap * jnp.tanh(s / softcap)
    return s


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc, m, l, *,
                scale, causal, window, softcap, bq, bk, nkv, kv_len=0):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m[...] = jnp.full_like(m, NEG_INF)
        l[...] = jnp.zeros_like(l)

    i = pl.program_id(1)
    qpos = i * bq + jax.lax.iota(jnp.int32, bq)
    kpos = j * bk + jax.lax.iota(jnp.int32, bk)

    # skip kv blocks that the causal/window mask fully excludes
    run = jnp.asarray(True)
    if causal:
        run &= (j * bk) <= ((i + 1) * bq - 1)
    if window:
        run &= ((j + 1) * bk - 1) > (i * bq - window)

    @pl.when(run)
    def _step():
        s = _scores(q_ref[0], k_ref[0], scale, softcap)      # (bq, bk) f32
        ok = _mask(qpos, kpos, causal, window, kv_len)
        s = jnp.where(ok, s, NEG_INF)
        m_new = jnp.maximum(m[...], s.max(axis=-1, keepdims=True))
        # guard fully-masked rows (exp(NEG_INF - NEG_INF) = 1 otherwise)
        alive = m_new > NEG_INF / 2
        p = jnp.where(alive, jnp.exp(s - m_new), 0.0)
        c = jnp.where(alive, jnp.exp(m[...] - m_new), 1.0)
        l[...] = l[...] * c + p.sum(axis=-1, keepdims=True)
        acc[...] = acc[...] * c + jax.lax.dot_general(
            p, v_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m[...] = m_new

    @pl.when(j == nkv - 1)
    def _fin():
        safe_l = jnp.maximum(l[...], 1e-30)
        o_ref[0] = (acc[...] / safe_l).astype(o_ref.dtype)
        lse_ref[0] = (m[...] + jnp.log(safe_l)).T          # (1, bq) row


def flash_fwd(q, k, v, *, g: int, scale: float, causal: bool, window: int,
              softcap: float, bq: int = DEFAULT_BLOCK_Q,
              bk: int = DEFAULT_BLOCK_KV, kv_len: int = 0,
              interpret: bool | None = None):
    """q: (BH, Sq, D); k/v: (BHkv, Skv, D); g = Hq//Hkv (GQA group).
    Returns (o (BH, Sq, D), lse (BH, Sq) fp32). `interpret=None` resolves
    through the shared backend policy (compiled on TPU) — a hardcoded True
    here used to silently interpret on real hardware."""
    interpret = resolve_interpret(interpret)
    BH, Sq, D = q.shape
    _, Skv, _ = k.shape
    bq = min(bq, Sq)
    bk = min(bk, Skv)
    assert Sq % bq == 0 and Skv % bk == 0
    nq, nkv = Sq // bq, Skv // bk
    kern = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                             window=window, softcap=softcap, bq=bq, bk=bk,
                             nkv=nkv, kv_len=kv_len)
    kv_map = lambda b, i, j: (b // g, j, 0)
    o, lse = pl.pallas_call(
        kern,
        grid=(BH, nq, nkv),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, D), kv_map),
            pl.BlockSpec((1, bk, D), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 1, bq), lambda b, i, j: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Sq, D), q.dtype),
            jax.ShapeDtypeStruct((BH, 1, Sq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, D), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        name="flash_fwd",
        interpret=interpret,
    )(q, k, v)
    return o, lse[:, 0]


# ---------------------------------------------------------------------------
# backward: dq (grid over q blocks) and dk/dv (grid over kv blocks)
# ---------------------------------------------------------------------------


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   acc, *, scale, causal, window, softcap, bq, bk, nkv,
                   kv_len=0):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    i = pl.program_id(1)
    qpos = i * bq + jax.lax.iota(jnp.int32, bq)
    kpos = j * bk + jax.lax.iota(jnp.int32, bk)

    s = _scores(q_ref[0], k_ref[0], scale, softcap)
    ok = _mask(qpos, kpos, causal, window, kv_len)
    p = jnp.where(ok, jnp.exp(s - lse_ref[0].T), 0.0)            # (bq, bk)
    dp = jax.lax.dot_general(do_ref[0].astype(jnp.float32),
                             v_ref[0].astype(jnp.float32),
                             (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta_ref[0].T)                               # dL/ds
    if softcap:
        ds = ds * (1.0 - (s / softcap) ** 2)
    ds = ds * scale
    acc[...] += jax.lax.dot_general(ds, k_ref[0].astype(jnp.float32),
                                    (((1,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)

    @pl.when(j == nkv - 1)
    def _fin():
        dq_ref[0] = acc[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, acck, accv, *,
                    scale, causal, window, softcap, bq, bk, nq, kv_len=0):
    i = pl.program_id(2)          # q blocks innermost

    @pl.when(i == 0)
    def _init():
        acck[...] = jnp.zeros_like(acck)
        accv[...] = jnp.zeros_like(accv)

    j = pl.program_id(1)
    qpos = i * bq + jax.lax.iota(jnp.int32, bq)
    kpos = j * bk + jax.lax.iota(jnp.int32, bk)

    s = _scores(q_ref[0], k_ref[0], scale, softcap)
    ok = _mask(qpos, kpos, causal, window, kv_len)
    p = jnp.where(ok, jnp.exp(s - lse_ref[0].T), 0.0)            # (bq, bk)
    accv[...] += jax.lax.dot_general(p, do_ref[0].astype(jnp.float32),
                                     (((0,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)
    dp = jax.lax.dot_general(do_ref[0].astype(jnp.float32),
                             v_ref[0].astype(jnp.float32),
                             (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta_ref[0].T)
    if softcap:
        ds = ds * (1.0 - (s / softcap) ** 2)
    ds = ds * scale
    acck[...] += jax.lax.dot_general(ds, q_ref[0].astype(jnp.float32),
                                     (((0,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)

    @pl.when(i == nq - 1)
    def _fin():
        dk_ref[0] = acck[...].astype(dk_ref.dtype)
        dv_ref[0] = accv[...].astype(dv_ref.dtype)


def flash_bwd(q, k, v, o, lse, do, *, g: int, scale: float, causal: bool,
              window: int, softcap: float, bq: int = DEFAULT_BLOCK_Q,
              bk: int = DEFAULT_BLOCK_KV, kv_len: int = 0,
              interpret: bool | None = None):
    """Returns (dq (BH,Sq,D), dk_h (BH,Skv,D), dv_h (BH,Skv,D)) — dk/dv are
    per-q-head; the wrapper sums groups of g to get the kv-head grads."""
    interpret = resolve_interpret(interpret)
    BH, Sq, D = q.shape
    BHkv, Skv, _ = k.shape
    bq = min(bq, Sq)
    bk = min(bk, Skv)
    nq, nkv = Sq // bq, Skv // bk
    delta = (do.astype(jnp.float32) * o.astype(jnp.float32)).sum(-1)  # (BH,Sq)
    # (BH, 1, Sq): per-row statistics ride lane-dense (1, bq) blocks
    lse, delta = lse[:, None], delta[:, None]

    kv_map = lambda b, i, j: (b // g, j, 0)
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          window=window, softcap=softcap, bq=bq, bk=bk,
                          nkv=nkv, kv_len=kv_len),
        grid=(BH, nq, nkv),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),   # q
            pl.BlockSpec((1, bk, D), kv_map),                      # k
            pl.BlockSpec((1, bk, D), kv_map),                      # v
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),   # do
            pl.BlockSpec((1, 1, bq), lambda b, i, j: (b, 0, i)),   # lse
            pl.BlockSpec((1, 1, bq), lambda b, i, j: (b, 0, i)),   # delta
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        name="flash_bwd_dq",
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    kv_map2 = lambda b, j, i: (b // g, j, 0)
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          window=window, softcap=softcap, bq=bq, bk=bk,
                          nq=nq, kv_len=kv_len),
        grid=(BH, nkv, nq),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, j, i: (b, i, 0)),   # q
            pl.BlockSpec((1, bk, D), kv_map2),                     # k
            pl.BlockSpec((1, bk, D), kv_map2),                     # v
            pl.BlockSpec((1, bq, D), lambda b, j, i: (b, i, 0)),   # do
            pl.BlockSpec((1, 1, bq), lambda b, j, i: (b, 0, i)),   # lse
            pl.BlockSpec((1, 1, bq), lambda b, j, i: (b, 0, i)),   # delta
        ],
        out_specs=[
            pl.BlockSpec((1, bk, D), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Skv, D), k.dtype),
            jax.ShapeDtypeStruct((BH, Skv, D), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((bk, D), jnp.float32),
                        pltpu.VMEM((bk, D), jnp.float32)],
        name="flash_bwd_dkv",
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    return dq, dk, dv
