"""Jitted public wrappers for the Pallas kernels.

Handle padding to tile multiples, backend dispatch via
`repro.kernels.backend.KernelPolicy` (compiled on TPU, interpret/ref
elsewhere — override with `use_policy`), and expose drop-in callables for
the core library:
  - fbp_cn           : plugs into repro.core.decode.decode_llv(cn_fbp=...)
  - gf_matmul        : encode / syndrome matmuls
  - pim_mac          : quantized-MAC forward
  - attend_protected : fused GF-page paged attention (the serving hot path)

Each wrapper resolves its backend OUTSIDE the jit boundary (the inner
jitted impls take the resolved `interpret` flag as a static arg), so a
`with use_policy(...)` override always selects the right executable
instead of hitting a trace cached under an earlier policy. The per-call
`interpret: bool | None` keyword is retained as a low-level escape hatch;
prefer `KernelPolicy` / `use_policy` for mode selection.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from . import fbp as _fbp
from . import gf_matmul as _gfm
from . import pim_mac as _pm
from .backend import resolve_interpret as _resolve_interpret
from .backend import resolve_mode as _resolve_mode
from repro.analysis.sanitizer import (check_finite, check_gf_symbols,
                                      check_quant_scales, sanitizer_enabled)
from repro.core.llv import NEG_INF


# The GF matmul kernels feed the MXU int8 operands (Mosaic has no int32 x
# int32 matmul), so symbols and stored levels must fit int8: p <= 127.
# Larger fields belong on the exact host scan (MemoryController).
MAX_KERNEL_FIELD = 127


def _check_int8_field(p: int, what: str) -> None:
    if p > MAX_KERNEL_FIELD:
        raise ValueError(f"{what}: GF({p}) symbols do not fit the kernel's "
                         f"int8 operands (p <= {MAX_KERNEL_FIELD})")


def _pad_to(x, axis, multiple, value=0):
    pad = (-x.shape[axis]) % multiple
    if pad == 0:
        return x, 0
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value), pad


@functools.partial(jax.jit, static_argnames=("p", "tile_n", "interpret"))
def _fbp_cn_jit(m_hat: jnp.ndarray, p: int, tile_n: int,
                interpret: bool) -> jnp.ndarray:
    N, dc, _ = m_hat.shape
    # the kernel folds the batch onto (8, 128) f32 vreg tiles: round the
    # tile to whole vregs and to no more than the (rounded) batch, then pad
    # the batch FROM the chosen tile so it is a tile multiple by
    # construction (asserted below)
    vreg = _fbp.SUBLANES * _fbp.LANES
    tile = min(-(-tile_n // vreg), -(-N // vreg)) * vreg
    padded, pad = _pad_to(m_hat, 0, tile)
    assert padded.shape[0] % tile == 0, (
        f"padded N={padded.shape[0]} not divisible by tile={tile}")
    if pad:  # padded rows: identity messages (harmless)
        fill = jnp.full((pad,) + m_hat.shape[1:], NEG_INF, m_hat.dtype)
        fill = fill.at[..., 0].set(0.0)
        padded = padded.at[N:].set(fill)
    slots = padded.transpose(1, 2, 0).reshape(dc, p, -1, _fbp.LANES)
    out = _fbp.fbp_cn_pallas(slots, p, tile_n=tile, interpret=interpret)
    return out.reshape(dc, p, -1).transpose(2, 0, 1)[:N]


def fbp_cn(m_hat: jnp.ndarray, p: int, *, tile_n: int = _fbp.DEFAULT_TILE_N,
           interpret: bool | None = None) -> jnp.ndarray:
    """(N, dc, p) contribution-space messages -> reflected extrinsics."""
    return _fbp_cn_jit(m_hat, p, tile_n, _resolve_interpret(interpret))


def fbp_cn_batched(m_hat: jnp.ndarray, p: int, **kw) -> jnp.ndarray:
    """Adapter matching decode_llv's cn_fbp signature: (B, c, dc, p)."""
    B, c, dc, pp = m_hat.shape
    out = fbp_cn(m_hat.reshape(B * c, dc, pp), p, **kw)
    return out.reshape(B, c, dc, pp)


@functools.partial(jax.jit, static_argnames=("p", "bm", "bn", "bk",
                                             "interpret"))
def _gf_matmul_jit(a: jnp.ndarray, b: jnp.ndarray, p: int, bm: int, bn: int,
                   bk: int, interpret: bool) -> jnp.ndarray:
    M, K = a.shape
    _, N = b.shape
    # same int32 accumulator as scan_syndromes: every dot-product term is a
    # product of two symbols in [0, p), so K*(p-1)^2 must stay below 2^31
    # or the mod-p epilogue sees a wrapped sum and returns garbage
    assert K * (p - 1) ** 2 < 2 ** 31, (
        f"gf_matmul int32 bound exceeded: {K} * ({p}-1)^2 >= 2^31")
    _check_int8_field(p, "gf_matmul")
    bm_, bn_, bk_ = (min(bm, max(8, M)), min(bn, max(8, N)), min(bk, max(8, K)))
    a, b = a.astype(jnp.int8), b.astype(jnp.int8)
    a, _ = _pad_to(a, 0, bm_)
    a, _ = _pad_to(a, 1, bk_)
    b, _ = _pad_to(b, 0, bk_)
    b, _ = _pad_to(b, 1, bn_)
    out = _gfm.gf_matmul_pallas(a, b, p, bm=bm_, bn=bn_, bk=bk_,
                                interpret=interpret)
    return out[:M, :N]


def gf_matmul(a: jnp.ndarray, b: jnp.ndarray, p: int, *, bm: int = 128,
              bn: int = 128, bk: int = 128,
              interpret: bool | None = None) -> jnp.ndarray:
    """(a @ b) % p with padding to MXU-aligned blocks."""
    if sanitizer_enabled():
        check_gf_symbols(a, p, "gf_matmul lhs")
        check_gf_symbols(b, p, "gf_matmul rhs")
    return _gf_matmul_jit(a, b, p, bm, bn, bk, _resolve_interpret(interpret))


def encode_words(u: jnp.ndarray, P: jnp.ndarray, p: int, *, bm: int = 128,
                 bn: int = 128, bk: int = 128,
                 interpret: bool | None = None) -> jnp.ndarray:
    """Device-side systematic encode: (B, k) info symbols in [0, p) and the
    code's (k, c) check generator -> (B, k + c) codewords [u | (u·P) mod p].

    The check matmul runs through the Pallas `gf_matmul` MXU path (mod-p
    fused into the last K-step), so encoding a page of words never leaves
    the device — this is the write hot path of
    `repro.memory.paged.PagedProtectedStore`. Bit-exact against the host
    `repro.core.np_encode_words` (`kernels.ref.encode_words_ref` is the
    tested oracle).
    """
    checks = gf_matmul(u, P, p, bm=bm, bn=bn, bk=bk, interpret=interpret)
    return jnp.concatenate([u.astype(jnp.int32), checks], axis=-1)


@functools.partial(jax.jit, static_argnames=("p", "bm", "bk", "interpret"))
def _scan_syndromes_jit(y: jnp.ndarray, ht: jnp.ndarray, p: int, bm: int,
                        bk: int, interpret: bool) -> jnp.ndarray:
    M, K = y.shape
    _, C = ht.shape
    # the kernel accumulator is int32: every syndrome sum is bounded by
    # K*(p-1)^2, which must stay below 2^31 or flags silently wrap. The
    # controller routes such codes to its exact int64 host path.
    assert K * (p - 1) ** 2 < 2 ** 31, (
        f"scan_syndromes int32 bound exceeded: {K} * ({p}-1)^2 >= 2^31")
    _check_int8_field(p, "scan_syndromes")
    bm_, bk_ = min(bm, max(8, M)), min(bk, max(8, K))
    y, ht = y.astype(jnp.int8), ht.astype(jnp.int8)
    y, _ = _pad_to(y, 0, bm_)
    y, _ = _pad_to(y, 1, bk_)
    ht, _ = _pad_to(ht, 0, bk_)
    ht, _ = _pad_to(ht, 1, _gfm.FLAG_LANES)
    out = _gfm.scan_syndromes_pallas(y, ht, p, bm=bm_, bk=bk_,
                                     interpret=interpret)
    return out[:M, 0] != 0


def scan_syndromes(y: jnp.ndarray, ht: jnp.ndarray, p: int, *, bm: int = 128,
                   bk: int = 128,
                   interpret: bool | None = None) -> jnp.ndarray:
    """Fused scrub syndrome scan: (B, n) words x (n, c) Hᵀ -> (B,) bool flags.

    flags[i] = any((y[i] @ ht) % p != 0); the mod + any reduction is fused
    into the matmul's last K-step so only the mask leaves the kernel. Pad
    rows (zero words are valid codewords) and pad check columns (all-zero
    Hᵀ columns accumulate 0 ≡ 0 mod p) can never raise a flag.
    """
    if sanitizer_enabled():
        check_gf_symbols(y, p, "scan_syndromes words")
    return _scan_syndromes_jit(y, ht, p, bm, bk, _resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("row_parallelism", "adc_levels",
                                             "bm", "bn", "interpret"))
def _pim_mac_jit(x: jnp.ndarray, w: jnp.ndarray, row_parallelism: int,
                 adc_levels: int, bm: int, bn: int,
                 interpret: bool) -> jnp.ndarray:
    B, K = x.shape
    _, N = w.shape
    R = row_parallelism if row_parallelism > 0 else K
    x, _ = _pad_to(x, 1, R)           # zero rows contribute clip(0)=0
    w, _ = _pad_to(w, 0, R)
    # zero-pad each group to a width that tiles the 128-lane K block: a
    # power of two up to 128 (several groups per block), else a multiple
    # of 128 (one group per block)
    Rp = 2 ** math.ceil(math.log2(R)) if R <= 128 else -(-R // 128) * 128
    gpb = max(1, 128 // Rp)
    g = x.shape[1] // R
    x = jnp.pad(x.reshape(B, g, R), ((0, 0), (0, 0), (0, Rp - R)))
    w = jnp.pad(w.reshape(g, R, N), ((0, 0), (0, Rp - R), (0, 0)))
    x, w = x.reshape(B, g * Rp), w.reshape(g * Rp, N)
    x, _ = _pad_to(x, 1, Rp * gpb)    # whole zero groups: exact no-ops
    w, _ = _pad_to(w, 0, Rp * gpb)
    bm_, bn_ = min(bm, max(8, B)), min(bn, max(8, N))
    x, _ = _pad_to(x, 0, bm_)
    w, _ = _pad_to(w, 1, bn_)
    out = _pm.pim_mac_pallas(x.astype(jnp.int8), w.astype(jnp.int8),
                             row_parallelism=Rp, adc_levels=adc_levels,
                             bm=bm_, bn=bn_, groups_per_block=gpb,
                             interpret=interpret)
    return out[:B, :N]


def pim_mac(x: jnp.ndarray, w: jnp.ndarray, *, row_parallelism: int = 0,
            adc_levels: int = 0, bm: int = 128, bn: int = 128,
            interpret: bool | None = None) -> jnp.ndarray:
    """Row-group-quantized MAC (B, K) x (K, N) -> (B, N) int32. Inputs and
    cell values reach the MXU as int8, so they must lie in [-128, 127]."""
    return _pim_mac_jit(x, w, row_parallelism, adc_levels, bm, bn,
                        _resolve_interpret(interpret))


# ---------------------------------------------------------------------------
# fused protected paged attention (the one-kernel serving hot path)
# ---------------------------------------------------------------------------


def np_bucket(n: int) -> int:
    """Page-count bucket: next power of two (min 1). The fused executable's
    shapes include the page axis, so serving pads the page stack to a
    bucket with zero pages (valid codewords, scale 0, valid 0 — exact
    no-ops in the online-softmax recurrence) and one trace serves a whole
    range of sequence lengths instead of retracing on every page freeze."""
    return 1 if n <= 1 else 2 ** math.ceil(math.log2(n))


@functools.partial(jax.jit, static_argnames=("p", "k_info", "page_shape",
                                             "softcap", "with_hot"))
def _attend_protected_ref_jit(q, kpages, vpages, kscales, vscales, valid,
                              hot_k, hot_v, hot_valid, *, p, k_info,
                              page_shape, softcap, with_hot):
    from .ref import attend_protected_ref
    return attend_protected_ref(
        q, kpages, vpages, kscales, vscales, valid, hot_k, hot_v, hot_valid,
        p=p, k_info=k_info, page_shape=page_shape, softcap=softcap,
        with_hot=with_hot)


@functools.partial(jax.jit, static_argnames=("p", "k_info", "page_shape",
                                             "softcap", "with_hot",
                                             "interpret"))
def _attend_protected_kernel_jit(q, kpages, vpages, kscales, vscales, valid,
                                 hot_k, hot_v, hot_valid, *, p, k_info,
                                 page_shape, softcap, with_hot, interpret):
    from . import paged_attention as _pa
    return _pa.attend_protected_pallas(
        q, kpages, vpages, kscales, vscales, valid, hot_k, hot_v, hot_valid,
        p=p, k_info=k_info, page_shape=page_shape, softcap=softcap,
        with_hot=with_hot, interpret=interpret)


def attend_protected(q, kpages, vpages, kscales, vscales, valid,
                     hot_k, hot_v, hot_valid, *, p: int, k_info: int,
                     page_shape, softcap: float = 0.0, with_hot: bool = True,
                     policy=None):
    """Fused protected paged attention: corrected GF pages + quantization
    scales + query block -> attention output in one executable.

    q: (B, Sq, Hq, D). kpages/vpages: (NP, S, W, n) int32 corrected GF
    pages — page step j is S sub-pages of `page_shape` = (Bsub, T, Hkv, D)
    stacked along batch (S·Bsub = B). kscales/vscales: (NP, S) f32 absmax
    scales; valid: (NP, B) int32 per-step per-row valid token counts.
    hot_k/hot_v: (B, T, Hkv, D) dense hot page applied last when
    `with_hot`, filled to hot_valid (B,).

    Dispatch follows `policy` (default: the ambient `KernelPolicy`): the
    jnp oracle graph in ref mode — bit-exact vs the unfused streaming path
    (`repro.nn.layers._attend_paged`) by shared-recurrence construction —
    or the Pallas kernel (`kernels/paged_attention.py`, fp32 in-VMEM math,
    allclose parity) compiled / interpreted otherwise. The page axis is
    padded to `np_bucket(NP)` with no-op zero pages so one trace serves a
    range of page counts.
    """
    NP = kpages.shape[0]
    B = q.shape[0]
    valid = jnp.asarray(valid, jnp.int32).reshape(max(NP, 0), B)
    hot_valid = jnp.asarray(hot_valid, jnp.int32).reshape(B)
    NB = np_bucket(NP)
    if NB != NP:
        pad = NB - NP

        def zpad(x):
            return jnp.concatenate(
                [x, jnp.zeros((pad,) + x.shape[1:], x.dtype)]) if NP else \
                jnp.zeros((pad,) + x.shape[1:], x.dtype)

        kpages, vpages = zpad(kpages), zpad(vpages)
        kscales, vscales = zpad(kscales), zpad(vscales)
        valid = zpad(valid)
    kw = dict(p=int(p), k_info=int(k_info), page_shape=tuple(page_shape),
              softcap=float(softcap or 0.0), with_hot=bool(with_hot))
    if sanitizer_enabled():
        check_gf_symbols(kpages, p, "attend_protected K pages")
        check_gf_symbols(vpages, p, "attend_protected V pages")
        check_quant_scales(kscales, "attend_protected K scales")
        check_quant_scales(vscales, "attend_protected V scales")
        check_finite(q, "attend_protected query")
    mode = _resolve_mode(policy)
    if mode == "ref":
        out = _attend_protected_ref_jit(
            q, kpages, vpages, kscales, vscales, valid, hot_k, hot_v,
            hot_valid, **kw)
    else:
        out = _attend_protected_kernel_jit(
            q, kpages, vpages, kscales, vscales, valid, hot_k, hot_v,
            hot_valid, interpret=(mode != "compiled"), **kw)
    if sanitizer_enabled():
        # a NaN that slipped into K/V/hot poisons the online-softmax
        # m/l/acc recurrence without raising — surface it here
        check_finite(out, "attend_protected output")
    return out


# ---------------------------------------------------------------------------
# flash attention (fwd + bwd Pallas kernels, custom_vjp)
# ---------------------------------------------------------------------------

from . import flash_attention as _fa


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(q, k, v, causal=True, window=0, softcap=0.0,
                    scale=None, interpret=None):
    """Flash attention with GQA. q: (B,Sq,Hq,D); k/v: (B,Skv,Hkv,D).
    Returns (B,Sq,Hq,D) in q.dtype. O(S*D) HBM traffic (see kernel docs)."""
    o, _ = _flash_fwd_rule(q, k, v, causal, window, softcap, scale, interpret)
    return o


def _fold(x):
    B, S, H, D = x.shape
    return x.transpose(0, 2, 1, 3).reshape(B * H, S, D)


def _unfold(x, B, H):
    BH, S, D = x.shape
    return x.reshape(B, H, S, D).transpose(0, 2, 1, 3)


def _pad_seq(x, mult):
    S = x.shape[1]
    pad = (-S) % mult
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
    return x, S


def _flash_fwd_rule(q, k, v, causal, window, softcap, scale, interpret):
    interpret = _resolve_interpret(interpret)
    B, Sq, Hq, D = q.shape
    _, Skv, Hkv, _ = k.shape
    g = Hq // Hkv
    sc = scale if scale is not None else 1.0 / (D ** 0.5)
    bq = min(_fa.DEFAULT_BLOCK_Q, Sq)
    bk = min(_fa.DEFAULT_BLOCK_KV, Skv)
    qp, _ = _pad_seq(q, bq)
    kp, _ = _pad_seq(k, bk)
    vp, _ = _pad_seq(v, bk)
    kv_len = Skv if kp.shape[1] != Skv else 0
    o2, lse = _fa.flash_fwd(_fold(qp), _fold(kp), _fold(vp), g=g, scale=sc,
                            causal=causal, window=window, softcap=softcap,
                            bq=bq, bk=bk, kv_len=kv_len, interpret=interpret)
    o = _unfold(o2, B, Hq)[:, :Sq]
    return o, (q, k, v, o, lse[:, :Sq])


def _flash_bwd_rule(causal, window, softcap, scale, interpret, res, do):
    q, k, v, o, lse = res
    interpret = _resolve_interpret(interpret)
    B, Sq, Hq, D = q.shape
    _, Skv, Hkv, _ = k.shape
    g = Hq // Hkv
    sc = scale if scale is not None else 1.0 / (D ** 0.5)
    bq = min(_fa.DEFAULT_BLOCK_Q, Sq)
    bk = min(_fa.DEFAULT_BLOCK_KV, Skv)
    qp, _ = _pad_seq(q, bq)
    kp, _ = _pad_seq(k, bk)
    vp, _ = _pad_seq(v, bk)
    op, _ = _pad_seq(o, bq)
    dop, _ = _pad_seq(do, bq)
    kv_len = Skv if kp.shape[1] != Skv else 0
    Sqp = qp.shape[1]
    lsep = lse
    if Sqp != Sq:
        lsep = jnp.pad(lse, ((0, 0), (0, Sqp - Sq)))
    dq2, dk2, dv2 = _fa.flash_bwd(_fold(qp), _fold(kp), _fold(vp), _fold(op),
                                  lsep, _fold(dop), g=g, scale=sc,
                                  causal=causal, window=window,
                                  softcap=softcap, bq=bq, bk=bk,
                                  kv_len=kv_len, interpret=interpret)
    Skvp = kp.shape[1]
    dq = _unfold(dq2, B, Hq)[:, :Sq]
    # dk/dv are per-q-head: sum each GQA group back to its kv head
    dk = _unfold(dk2, B, Hq)[:, :Skv].reshape(B, Skv, Hkv, g, D).sum(3)
    dv = _unfold(dv2, B, Hq)[:, :Skv].reshape(B, Skv, Hkv, g, D).sum(3)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


flash_attention.defvjp(_flash_fwd_rule, _flash_bwd_rule)
