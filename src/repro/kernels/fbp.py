"""Pallas TPU kernel: check-node Forward-Backward Propagation (paper §3.2.2).

The ASIC runs one CN serially over its D_C incident LLV groups; the TPU analogue
batches thousands of independent (codeword × CN) FBP problems across VPU lanes.

Layout: messages arrive as (dc, p, R, 128) float32 in contribution space — the
batch axis is folded onto whole (sublane, lane) vreg tiles and the slot and GF
axes are leading, so every GF element of every slot message is one full
(tile_r, 128) tile. (With the GF axis on the lanes, p=3 padded each row about
40x and a check degree of 15 overflowed VMEM.) dc and p are small
compile-time constants, so the FM/BM chains fully unroll into elementwise
vector ops over whole tiles.

The cyclic max-plus convolution over the GF axis, out[k] = max_j
a[(k - j) % p] + b[j], is then p shifted adds and maxes over whole
(p, tile_r, 128) messages: the cyclic shift and the final reflection
k -> -k move whole tiles along the leading GF axis, with no lane shuffles.

The chain over dc is inherently serial (it IS the algorithm, paper Fig. 3(c));
parallelism comes from the batch dimension, mirroring the paper's N_VI-way VN
array feeding one shared CN.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.llv import NEG_INF

from .backend import resolve_interpret

LANES = 128
SUBLANES = 8                        # float32 vreg tile is (8, 128)
DEFAULT_TILE_N = 32 * LANES         # batch elements per grid step


def _shift(a, j: int, p: int):
    """Cyclic shift along the leading GF axis: out[k] = a[(k - j) % p].
    A concat of two static leading-axis slices — whole-tile moves."""
    j %= p
    if j == 0:
        return a
    return jnp.concatenate([a[p - j:], a[:p - j]], axis=0)


def _conv(a, b, p: int):
    """Cyclic max-plus convolution of two (p, tile_r, 128) messages:
    out[k] = max_j a[(k - j) % p] + b[j]."""
    acc = a + b[0:1]
    for j in range(1, p):
        acc = jnp.maximum(acc, _shift(a, j, p) + b[j:j + 1])
    return acc


def _fbp_kernel(m_ref, o_ref, *, dc: int, p: int):
    # m_ref/o_ref: (dc, p, tile_r, 128) VMEM blocks; msgs[t] is slot t's
    # (p, tile_r, 128) message, one full tile per GF element
    msgs = [m_ref[t] for t in range(dc)]

    fm = [msgs[0]]
    for t in range(1, dc):
        fm.append(_conv(fm[-1], msgs[t], p))
    bm_rev = [msgs[dc - 1]]
    for t in range(dc - 2, -1, -1):
        bm_rev.append(_conv(msgs[t], bm_rev[-1], p))
    bm = bm_rev[::-1]                      # bm[t] = conv of slots t..dc-1

    for t in range(dc):
        if dc == 1:
            k = jax.lax.broadcasted_iota(jnp.int32, m_ref.shape[1:], 0)
            ext = jnp.where(k == 0, jnp.zeros((), m_ref.dtype),
                            jnp.full((), NEG_INF, m_ref.dtype))
        elif t == 0:
            ext = bm[1]
        elif t == dc - 1:
            ext = fm[dc - 2]
        else:
            ext = _conv(fm[t - 1], bm[t + 1], p)
        # reflect: out[k] = ext[(-k) % p] (sum of others must equal -u_t)
        for k in range(p):
            o_ref[t, k] = ext[(-k) % p]


def fbp_cn_pallas(m_hat: jnp.ndarray, p: int, *, tile_n: int = DEFAULT_TILE_N,
                  interpret: bool | None = None) -> jnp.ndarray:
    """m_hat: (dc, p, R, 128) slot-major messages, R*128 batch elements ->
    reflected extrinsic messages in the same layout.

    R is padded to a tile multiple by the caller (`ops.fbp_cn`, which also
    does the (N, dc, p) <-> (dc, p, R, 128) transposes); `tile_n` is a
    multiple of 8*128. `interpret` defaults to the shared backend dispatch
    (compiled on TPU, interpreted elsewhere) so direct callers match
    `ops.fbp_cn`.
    """
    dc, pp, R, lanes = m_hat.shape
    assert pp == p and lanes == LANES
    assert tile_n % (SUBLANES * LANES) == 0, tile_n
    tile_r = tile_n // LANES
    assert R % tile_r == 0, f"R={R} not a multiple of tile rows {tile_r}"
    kern = functools.partial(_fbp_kernel, dc=dc, p=p)
    spec = pl.BlockSpec((dc, p, tile_r, LANES), lambda i: (0, 0, i, 0))
    return pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct(m_hat.shape, m_hat.dtype),
        in_specs=[spec],
        out_specs=spec,
        grid=(R // tile_r,),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        name="fbp_cn",
        interpret=resolve_interpret(interpret),
    )(m_hat)
