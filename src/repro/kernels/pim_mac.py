"""Pallas TPU kernel: bit-line PIM MAC with per-row-group ADC quantization.

Models the analog accumulate + flash-ADC sample path (paper §2.1, Fig. 1(a)):
partial sums over `row_parallelism` wordlines are clipped to the ADC range
before digital accumulation. On TPU this is a K-blocked int8 matmul (int32
accumulate) whose K-block holds `groups_per_block` whole row-parallelism
groups, with the clip fused between the MXU dot and the accumulate — the
quantization epilogue rides in VMEM, never spilling partials to HBM. Each
group's partial sum is one full-width dot over the block with the other
groups' lanes masked to zero, so a group narrower than the 128-lane tile
needs no lane slicing; the caller (`ops.pim_mac`) zero-pads every group to
a width that tiles the block.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .backend import resolve_interpret


def _pim_mac_kernel(x_ref, w_ref, o_ref, *, groups_per_block: int, R: int,
                    adc_half: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...]                          # (bm, bk), bk = groups_per_block*R
    w = w_ref[...]                          # (bk, bn)
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    acc = jnp.zeros_like(o_ref)
    for g in range(groups_per_block):
        xs = x
        if groups_per_block > 1:
            xs = jnp.where((lane >= g * R) & (lane < (g + 1) * R), x,
                           jnp.zeros_like(x))
        partial = jax.lax.dot_general(
            xs, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32)
        if adc_half > 0:
            partial = jnp.clip(partial, -adc_half, adc_half)
        acc += partial
    o_ref[...] += acc


def pim_mac_pallas(x: jnp.ndarray, w: jnp.ndarray, *, row_parallelism: int,
                   adc_levels: int, bm: int = 128, bn: int = 128,
                   groups_per_block: int = 1,
                   interpret: bool | None = None) -> jnp.ndarray:
    """x: (B, K) int8, w: (K, N) int8 -> (B, N) int32 group-quantized MAC.

    K must be a multiple of row_parallelism * groups_per_block (caller pads —
    zero rows are exact no-ops for the clip since clip(0)=0 contributes 0).
    """
    B, K = x.shape
    K2, N = w.shape
    assert K == K2
    R = row_parallelism if row_parallelism > 0 else K
    bk = R * groups_per_block
    assert K % bk == 0, f"K={K} not a multiple of group block {bk}"
    assert B % bm == 0 and N % bn == 0
    nk = K // bk
    kern = functools.partial(_pim_mac_kernel, groups_per_block=groups_per_block,
                             R=R, adc_half=adc_levels // 2 if adc_levels > 0 else 0)
    return pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct((B, N), jnp.int32),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        grid=(B // bm, N // bn, nk),
        name="pim_mac",
        interpret=resolve_interpret(interpret),
    )(x, w)
