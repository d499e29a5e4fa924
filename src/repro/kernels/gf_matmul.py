"""Pallas TPU kernels: GF(p) matrix multiply (encode / syndrome) and the
fused scrub syndrome scan.

`w · H_G` (encode, paper Fig. 2(b)) and `Y' · H_Cᵀ` (syndrome, paper Eq. 3/5)
are integer matmuls with a mod-p epilogue. The ASIC uses mux-based sparse
routing; the TPU-idiomatic equivalent is a dense MXU matmul tiled 128×128 with
the mod fused into the final K-step (DESIGN.md §3).

`scan_syndromes_pallas` is the memory-mode scrub hot path (`H·yᵀ mod p` over
every stored word, paper §3 / ROADMAP "Pallas scrub kernel"): the same
K-blocked MXU accumulation, but the mod-p + nonzero-any reduction over the
check dimension is fused into the last K-step, so only a (B,) flagged mask
leaves the kernel — the full syndrome matrix never exists outside VMEM.

Operands reach the MXU as int8 (Mosaic has no int32 x int32 matmul) and
accumulate in int32: field symbols and stored levels fit int8 for p <= 127,
and the callers in `ops` cast and guard that, plus the int32 accumulator
bound K*(p-1)^2 < 2^31.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .backend import resolve_interpret

# lane width of the flag output block: flags are per-row scalars, but TPU
# blocks need a 128-wide minor dim; the wrapper slices column 0.
FLAG_LANES = 128


def _gf_matmul_kernel(a_ref, b_ref, o_ref, *, p: int, nk: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += jax.lax.dot_general(
        a_ref[...], b_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)

    @pl.when(k == nk - 1)
    def _epilogue():
        o_ref[...] = o_ref[...] % p


def gf_matmul_pallas(a: jnp.ndarray, b: jnp.ndarray, p: int, *,
                     bm: int = 128, bn: int = 128, bk: int = 128,
                     interpret: bool | None = None) -> jnp.ndarray:
    """(a @ b) % p. a: (M, K) int8, b: (K, N) int8 -> (M, N) int32.

    The output block is revisited across the K grid dimension (accumulate in
    VMEM, mod-p epilogue on the last step). Caller (`ops.gf_matmul`) pads
    M/N/K to block multiples.
    """
    M, K = a.shape
    K2, N = b.shape
    assert K == K2
    assert M % bm == 0 and N % bn == 0 and K % bk == 0
    nk = K // bk
    kern = functools.partial(_gf_matmul_kernel, p=p, nk=nk)
    return pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.int32),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        grid=(M // bm, N // bn, nk),
        name="gf_matmul",
        interpret=resolve_interpret(interpret),
    )(a, b)


def _scan_syndromes_kernel(y_ref, ht_ref, o_ref, acc_ref, *, p: int, nk: int):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        y_ref[...], ht_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)

    @pl.when(k == nk - 1)
    def _flag():
        nz = ((acc_ref[...] % p) != 0).astype(jnp.int32)
        o_ref[...] = jnp.broadcast_to(
            jnp.max(nz, axis=1, keepdims=True), o_ref.shape)


def scan_syndromes_pallas(y: jnp.ndarray, ht: jnp.ndarray, p: int, *,
                          bm: int = 128, bk: int = 128,
                          interpret: bool | None = None) -> jnp.ndarray:
    """Fused scrub scan: flags[i] = any((y[i] @ ht) % p != 0).

    y: (M, K) int8 stored level-words, ht: (K, C) int8 check matrix
    transpose ->
    (M, FLAG_LANES) int32 with the per-word flag broadcast across lanes
    (callers read column 0). The (bm, C) syndrome accumulator lives in VMEM
    scratch and is reduced in the last K-step — the syndrome matrix is never
    written to HBM. Caller (`ops.scan_syndromes`) pads M/K to block multiples
    and C to a lane multiple.
    """
    M, K = y.shape
    K2, C = ht.shape
    assert K == K2
    assert M % bm == 0 and K % bk == 0 and C % FLAG_LANES == 0
    nk = K // bk
    kern = functools.partial(_scan_syndromes_kernel, p=p, nk=nk)
    return pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct((M, FLAG_LANES), jnp.int32),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, k: (i, k)),
            pl.BlockSpec((bk, C), lambda i, k: (k, 0)),
        ],
        out_specs=pl.BlockSpec((bm, FLAG_LANES), lambda i, k: (i, 0)),
        scratch_shapes=[pltpu.VMEM((bm, C), jnp.int32)],
        grid=(M // bm, nk),
        name="scan_syndromes",
        interpret=resolve_interpret(interpret),
    )(y, ht)
