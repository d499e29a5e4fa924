"""Fused protected paged-attention Pallas kernel — the one-kernel serving
hot path.

The unfused serving read path interrupts the dataflow three times per KV
page: decode the GF page to symbols (HBM), dequantize to bf16 K/V (HBM),
then run one online-softmax update (HBM in, HBM out). This kernel takes
the *corrected GF pages themselves* plus their quantization scales and the
query block, and produces the attention output directly: per-page base-p
desymbolize + int8 dequant feed the flash-attention recurrence in VMEM
scratch, so corrected K/V never round-trips HBM — the paper's
no-dataflow-interruption property applied to serving.

Division of labor with the store: syndrome scanning and FBP correction of
*flagged* pages happen upstream (`PagedProtectedStore.read_page_corrected`,
the scan-gated fast path — clean pages skip the decoder entirely and most
pages are clean), so the pages this kernel consumes are already corrected
symbols; the kernel fuses everything after correction — desymbolize,
dequant, QKᵀ, online softmax, ·V accumulate.

Layout (page-granular flash attention, following `flash_attention.py`):
page step j is S sub-pages of shape `page_shape` = (Bsub, T, Hkv, D)
stacked along batch (S=1 for the single-tenant layer, S=B for the serving
engine's per-slot pages). grid = (S, NP): sub-page s owns the Bsub batch
rows it stores, and the page axis is innermost, with the output block and
the fp32 (m, l, acc) running state in VMEM scratch revisited along it.
Step (s, j) loads ONE (W, n) GF page — VMEM use does not grow with the
batch — plus sub-page s's slice of q and of the hot page; the per-(page,
row) scales and valid counts sit in SMEM. The dense hot page (tokens not
yet frozen into GF storage) is applied as a final update inside the same
kernel, and the last page step writes `acc / l`.

Dequant runs on the MXU. The little-endian base-p digit stream of g
consecutive words holds exactly rg rows of D values (g·k = rg·D·digits),
so the rows of word group r are Σ_c words[g·r + c] @ M_c, with constant
weight matrices M_c (n, rg·D) holding 0 or p^i. Word g·r + c is picked
by a one-hot matmul (Mosaic has no strided sublane load from a page whose
width is not 128 lanes), so dequant is 2g small matmuls instead of a
lane-misaligned (W, k) -> (numel, digits) relayout. Digits (<= p-1) and
weights (p^i <= 256) are exact in bf16 and their sums in f32, so the
bytes are exact.

Attention folds heads into the matmul rows: q rows are (bb, sq, hq) and
K/V rows (bb, t, h), and one (rows_q, rows_kv) score block is masked to
same-batch-row, same-GQA-group, valid-token entries — plain 2-D MXU
matmuls, no in-kernel head transposes.

In-kernel math is fp32 end-to-end (no bf16 round-trip between dequant and
QKᵀ — the corrected K/V exist only as VMEM fp32), so parity vs the
bit-exact jnp oracle (`ref.attend_protected_ref`, which replicates the
unfused path's bf16 casts) is allclose at bf16 tolerance, asserted by
tests/test_fused_attention.py; tests/test_chip_compile.py compiles it for
a TPU v5e at serving widths.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .backend import resolve_interpret

NEG_INF = -1e30


def _digit_groups(p: int, k_info: int, D: int) -> tuple[int, int, int]:
    """(g, rg, digits): g info words carry exactly rg rows of D values,
    each value `digits` base-p digits."""
    digits = math.ceil(8.0 / math.log2(p))
    span = math.lcm(digits * D, k_info)
    return span // k_info, span // (digits * D), digits


def _dequant(page_ref, scale, *, p, k_info, rows, D):
    """(1, 1, W, n) int32 GF page ref + scalar scale -> (rows, D) fp32.

    Replicates `repro.memory.paged.dequantize_tensor`: systematic info
    symbols, digits clipped into the field, little-endian base-p
    desymbolize mod 256, int8 recentre, absmax scale. Word group r holds
    rows rg·r .. rg·r+rg-1; its c-th word is picked by the one-hot
    Sel_c[r, w] = (w == g·r + c), and M_c[col, e] = p^i where
    e·digits + i == c·k_info + col (parity columns col >= k_info weigh 0).
    """
    W, n = page_ref.shape[-2:]
    g, rg, digits = _digit_groups(p, k_info, D)
    ng = rows // rg
    words = jnp.clip(page_ref[0, 0], 0, p - 1).astype(jnp.bfloat16)
    grp = jax.lax.broadcasted_iota(jnp.int32, (ng, W), 0)
    wid = jax.lax.broadcasted_iota(jnp.int32, (ng, W), 1)
    col = jax.lax.broadcasted_iota(jnp.int32, (n, rg * D), 0)
    elt = jax.lax.broadcasted_iota(jnp.int32, (n, rg * D), 1)
    mm = functools.partial(jax.lax.dot_general,
                           dimension_numbers=(((1,), (0,)), ((), ())),
                           preferred_element_type=jnp.float32)
    val = jnp.zeros((ng, rg * D), jnp.float32)
    for c in range(g):
        sel = (wid == grp * g + c).astype(jnp.bfloat16)
        # one nonzero term per output: exact, and exact again in bf16
        picked = mm(sel, words).astype(jnp.bfloat16)       # (ng, n)
        i = c * k_info + col - elt * digits                # digit in byte
        w = jnp.zeros((n, rg * D), jnp.float32)
        for d in range(digits):
            w = jnp.where(i == d, jnp.float32(p ** d), w)
        w = jnp.where(col < k_info, w, 0.0).astype(jnp.bfloat16)
        val = val + mm(picked, w)
    val = val - 256.0 * jnp.floor(val / 256.0)             # exact: < 2^24
    out = (val - 128.0) * scale
    return out.reshape(rows, D) if rg > 1 else out


def _update(q, k, v, ok, m, l, acc, *, softcap):
    """One online-softmax update on the VMEM carries. q: (Mq, D) fp32;
    k/v: (Mk, D) fp32; ok: (Mq, Mk) bool."""
    D = q.shape[-1]
    logits = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    logits = logits / jnp.sqrt(jnp.float32(D))
    if softcap:
        logits = softcap * jnp.tanh(logits / softcap)
    logits = jnp.where(ok, logits, NEG_INF)
    new_m = jnp.maximum(m[...], logits.max(axis=-1, keepdims=True))
    w = jnp.exp(logits - new_m)
    corr = jnp.exp(m[...] - new_m)
    l[...] = corr * l[...] + w.sum(axis=-1, keepdims=True)
    acc[...] = corr * acc[...] + jax.lax.dot_general(
        w, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m[...] = new_m


def _kernel(ks_ref, vs_ref, valid_ref, hval_ref, kp_ref, vp_ref, q_ref,
            hk_ref, hv_ref, o_ref, m, l, acc, *, p, k_info, page_shape, S,
            Sq, Hq, softcap, nps, with_hot):
    s, j = pl.program_id(0), pl.program_id(1)
    Bsub, T, Hkv, Dh = page_shape
    G = Hq // Hkv
    B = S * Bsub
    Mq, Mk = Bsub * Sq * Hq, Bsub * T * Hkv

    @pl.when(j == 0)
    def _init():
        m[...] = jnp.full_like(m, -jnp.inf)
        l[...] = jnp.zeros_like(l)
        acc[...] = jnp.zeros_like(acc)

    # mask planes: q rows (bb, sq, hq), kv rows (bb, t, h)
    rq = jax.lax.broadcasted_iota(jnp.int32, (Mq, Mk), 0)
    rk = jax.lax.broadcasted_iota(jnp.int32, (Mq, Mk), 1)
    kb, kt = rk // (T * Hkv), (rk // Hkv) % T
    same = (rq // (Sq * Hq) == kb) & ((rq % Hq) // G == rk % Hkv)

    def valid_tokens(ref, base):
        """Per-kv-row token limit from the SMEM counts ref[base + bb]."""
        lim = jnp.zeros((Mq, Mk), jnp.int32)
        for bb in range(Bsub):
            lim = jnp.where(kb == bb, ref[base + bb], lim)
        return lim

    q = q_ref[0].astype(jnp.float32)
    kpg = _dequant(kp_ref, ks_ref[j * S + s], p=p, k_info=k_info, rows=Mk,
                   D=Dh)
    vpg = _dequant(vp_ref, vs_ref[j * S + s], p=p, k_info=k_info, rows=Mk,
                   D=Dh)
    ok = same & (kt < valid_tokens(valid_ref, j * B + s * Bsub))
    _update(q, kpg, vpg, ok, m, l, acc, softcap=softcap)

    @pl.when(j == nps - 1)
    def _fin():
        if with_hot:
            ok = same & (kt < valid_tokens(hval_ref, s * Bsub))
            _update(q, hk_ref[0].astype(jnp.float32),
                    hv_ref[0].astype(jnp.float32), ok, m, l, acc,
                    softcap=softcap)
        out = acc[...] / jnp.maximum(l[...], 1e-30)
        o_ref[0] = out.astype(o_ref.dtype)


def attend_protected_pallas(q, kpages, vpages, kscales, vscales, valid,
                            hot_k, hot_v, hot_valid, *, p: int, k_info: int,
                            page_shape, softcap: float = 0.0,
                            with_hot: bool = True,
                            interpret: bool | None = None):
    """Raw kernel entry point (shape contract in `ref.attend_protected_ref`;
    use `ops.attend_protected` for policy dispatch + page bucketing).
    kpages/vpages: (NP, S, W, n) with NP >= 1."""
    NP, S, W, n = kpages.shape
    B, Sq, Hq, Dh = q.shape
    Bsub, T, Hkv, _ = page_shape
    assert S * Bsub == B, (S, Bsub, B)
    assert p <= 256, "bf16-exact dequant needs p^(digits-1) <= 256"
    g, rg, _ = _digit_groups(p, k_info, Dh)
    Mq, Mk = Bsub * Sq * Hq, Bsub * T * Hkv
    assert Mk % rg == 0 and Mk // rg * g <= W, (
        f"page_shape {page_shape} does not fill whole {g}-word digit groups")
    kern = functools.partial(
        _kernel, p=p, k_info=k_info, page_shape=tuple(page_shape), S=S,
        Sq=Sq, Hq=Hq, softcap=softcap, nps=NP, with_hot=with_hot)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    page_spec = pl.BlockSpec((1, 1, W, n), lambda s, j: (j, s, 0, 0))
    per_sub = lambda rows_: pl.BlockSpec((1, rows_, Dh),
                                         lambda s, j: (s, 0, 0))
    out = pl.pallas_call(
        kern,
        grid=(S, NP),
        in_specs=[smem, smem, smem, smem, page_spec, page_spec,
                  per_sub(Mq), per_sub(Mk), per_sub(Mk)],
        out_specs=per_sub(Mq),
        out_shape=jax.ShapeDtypeStruct((S, Mq, Dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((Mq, 1), jnp.float32),
            pltpu.VMEM((Mq, 1), jnp.float32),
            pltpu.VMEM((Mq, Dh), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name="attend_protected",
        interpret=resolve_interpret(interpret),
    )(kscales.reshape(-1).astype(jnp.float32),
      vscales.reshape(-1).astype(jnp.float32),
      valid.reshape(-1).astype(jnp.int32),
      hot_valid.reshape(-1).astype(jnp.int32),
      kpages, vpages, q.reshape(S, Mq, Dh), hot_k.reshape(S, Mk, Dh),
      hot_v.reshape(S, Mk, Dh))
    return out.reshape(B, Sq, Hq, Dh)
