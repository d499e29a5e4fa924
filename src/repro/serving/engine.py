"""Multi-tenant protected serving engine: continuous batching over a shared
NB-LDPC-protected page pool.

PR 5's serving path protects ONE sequence: one `ProtectedKVCaches`, grow-only
pages, a Python loop per decode step. This module is the layer the ROADMAP's
"millions of users" item asks for — a vLLM-style engine that amortizes the
protected datapath across many concurrent sequences:

- **slots** — the engine owns `max_active` batch slots. Every jitted
  executable (embed, attention, head) runs at batch `max_active` whatever
  the occupancy, so admitting more tenants raises aggregate tokens/s at
  near-constant step latency (the scaling the multi-tenant benchmark
  measures), and a sequence's row-computation is independent of which other
  slots are occupied — single-tenant and 16-tenant runs of the same engine
  shape are bit-exact per tenant.
- **block tables** — each slot's K/V pages live in a shared
  `repro.memory.pool.ProtectedPagePool` through per-tenant `PooledStore`
  block tables (one store per slot per layer per K/V). Admission preflights
  pool capacity; a freeze that would exhaust the pool preempts the
  youngest sequence (vLLM-style LIFO preemption) instead of corrupting
  state, returning its blocks to the free list.
- **preemption / resume** — a preempted sequence keeps its token history
  only. Readmission re-prefills the original prompt and replays the
  generated tokens teacher-forced through the normal batched decode path,
  which reconstructs the exact quantize-on-freeze page contents — resumed
  sequences continue bit-exactly, concurrently with live tenants.
- **background scrub** — every `scrub_every` steps the engine runs a
  bounded `pool.scrub(max_pages=...)` sweep over cold pool pages between
  decode steps (the PR 4 iterator machinery, pool-wide), with repairs
  attributed to the owning tenant.
- **per-tenant accounting** — each slot's `PooledStore.stats` counts
  detected/corrected/uncorrectable on that tenant's reads; the engine
  aggregates them (plus the pool's per-owner scrub report) in
  `tenant_stats`.
- **observability** — under `repro.obs` ambient contexts each step emits
  an `engine.step` span (admit/prefill/decode/scrub children, preemption
  instants) to the Chrome-trace tracer, counters/latency histograms to
  the metrics registry (`publish_metrics` adds per-tenant gauges), and
  the RAS estimator both ingests scrub telemetry and drives the scrub
  schedule (adaptive interval + flag-hot page prioritization). With no
  telemetry installed every hook is a no-op attribute check.

The engine drives the unmodified model stack: `repro.models.lm.decode_step`
routes `EngineCaches` (duck-typed `ProtectedKVCaches` surface, (B,) per-slot
positions) through the same `_apply_block` / `KVSource.attend` code the
single-tenant path uses — by default the fused GF-page attention kernel
(`repro.kernels.ops.attend_protected`), with the streaming `_attend_paged`
path as the exact-parity fallback.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.memory.controller import ControllerStats
from repro.memory.pool import (PoolExhausted, PooledStore, ProtectedPagePool)
from repro.memory.paged import (dequantize_tensor, quantize_tensor,
                                words_for_tensor)
from repro.models.kv import ProtectedKVConfig
from repro.nn.kv_source import KVSource
from repro.nn.layers import CDT
from repro.obs import metrics as obs_metrics
from repro.obs import ras as obs_ras
from repro.obs import trace as obs_trace
from repro.obs.trace import span

__all__ = ["BatchedPagedKV", "BatchedDenseKV", "EngineCaches",
           "SequenceState", "ServingEngine"]


@jax.jit
def _scatter_rows(buf, rows, pos):
    """Per-slot scatter: buf (B,T,H,D), rows (B,1,H,D), pos (B,) — write
    row b at buf[b, pos[b]]. One cached executable serves every step."""
    return jax.vmap(
        lambda b, r, p: jax.lax.dynamic_update_slice_in_dim(b, r, p, axis=0)
    )(buf, rows, pos)


class BatchedPagedKV(KVSource):
    """One attention layer's K/V for `max_active` slots: a shared dense hot
    page block with per-slot fill levels, and per-slot pool-backed frozen
    pages (`PooledStore` block tables into the shared pool).

    Slots freeze independently — when slot b's hot row reaches `page_tokens`
    it alone is quantized + device-encoded into b's stores. Implements
    `KVSource`: the fused `attend` stacks per-slot *corrected GF codeword
    pages* into (NP, B, W, n) kernel operands (empty slots contribute zero
    pages with scale 0 and valid 0 — exact no-ops) and runs
    `ops.attend_protected`; the streaming `pages()` path stacks decoded
    (B, T, Hkv, D) steps for the per-page online-softmax and stays as the
    exact-parity reference. Rows are computation-independent, so a slot's
    attention output does not depend on the other slots' contents."""

    kind = "protected"

    def __init__(self, pkv: ProtectedKVConfig, pool: ProtectedPagePool,
                 max_active: int, hkv: int, dh: int, dtype=CDT):
        self.pkv, self.pool = pkv, pool
        self.max_active = max_active
        self.T = pkv.page_tokens
        self.code = pool.code
        self.dtype = dtype
        self.page_shape = (1, self.T, hkv, dh)
        wpu = words_for_tensor(self.page_shape, self.code.p, self.code.k)
        if wpu != pool.page_words:
            raise ValueError(
                f"pool page_words={pool.page_words} != {wpu} words per "
                f"per-slot KV page {self.page_shape}; size the pool with "
                "words_for_tensor((1, page_tokens, n_kv_heads, head_dim))")
        self.words_per_page = wpu
        self.hot_k = jnp.zeros((max_active, self.T, hkv, dh), dtype)
        self.hot_v = jnp.zeros((max_active, self.T, hkv, dh), dtype)
        self.hot_len = np.zeros(max_active, np.int32)
        self.k_stores: list[PooledStore | None] = [None] * max_active
        self.v_stores: list[PooledStore | None] = [None] * max_active
        self.metas: list[list] = [[] for _ in range(max_active)]
        self._decoded: list[list] = [[] for _ in range(max_active)]
        self._stack_cache: list | None = None
        # fused-path memos: per-slot corrected GF codeword pages and the
        # stacked (NP, B, W, n) kernel operands built from them
        self._gf_decoded: list[list] = [[] for _ in range(max_active)]
        self._gf_stack_cache = None
        # which slots advance on append; the engine sets this each step
        self.active = np.zeros(max_active, bool)

    # -- slot lifecycle -----------------------------------------------------

    def open_slot(self, b: int, owner=None) -> None:
        self.k_stores[b] = PooledStore(self.pool, owner=owner)
        self.v_stores[b] = PooledStore(self.pool, owner=owner)
        self.hot_k = self.hot_k.at[b].set(0.0)
        self.hot_v = self.hot_v.at[b].set(0.0)
        self.hot_len[b] = 0
        self.metas[b] = []
        self._decoded[b] = []
        self._gf_decoded[b] = []
        self._stack_cache = None
        self._gf_stack_cache = None

    def close_slot(self, b: int) -> dict:
        """Free the slot's pool blocks. Returns the slot's accumulated
        correction counters so the engine can bank them per tenant."""
        out: dict[str, int] = {}
        for store in (self.k_stores[b], self.v_stores[b]):
            if store is not None:
                ControllerStats.add_counts(out, store.stats)
                store.free()
        for k in ControllerStats.CORRECTION_KEYS:
            out.setdefault(k, 0)
        self.k_stores[b] = self.v_stores[b] = None
        self.hot_len[b] = 0
        self.metas[b] = []
        self._decoded[b] = []
        self._gf_decoded[b] = []
        self._stack_cache = None
        self._gf_stack_cache = None
        return out

    # -- write path ---------------------------------------------------------

    def _freeze_rows(self, b: int, kpage: jnp.ndarray,
                     vpage: jnp.ndarray) -> None:
        """Quantize + device-encode one (1, T, Hkv, D) page into slot b's
        stores (write-through memoizing the decoded view, like the
        single-tenant `ProtectedKVLayer._freeze`)."""
        p, kk = self.code.p, self.code.k
        kw, kmeta = quantize_tensor(kpage, p, kk)
        vw, vmeta = quantize_tensor(vpage, p, kk)
        self.k_stores[b].append_words(kw)
        self.v_stores[b].append_words(vw)
        self.metas[b].append((kmeta, vmeta))
        self._decoded[b].append((dequantize_tensor(kw, kmeta, p),
                                 dequantize_tensor(vw, vmeta, p)))
        # fused-path write-through: the store page just written IS the
        # corrected codeword page
        j = self.k_stores[b].n_pages - 1
        self._gf_decoded[b].append((self.k_stores[b].page(j),
                                    self.v_stores[b].page(j)))
        self._stack_cache = None
        self._gf_stack_cache = None

    def _freeze_slot(self, b: int) -> None:
        self._freeze_rows(b, self.hot_k[b:b + 1], self.hot_v[b:b + 1])
        self.hot_len[b] = 0   # stale hot rows are masked by valid and
                              # overwritten by the next scatters

    def ingest_slot(self, b: int, k: jnp.ndarray, v: jnp.ndarray) -> None:
        """Adopt a prompt's (1, S, Hkv, D) K/V into slot b: full pages
        freeze (quantize + encode), the remainder seeds the hot row."""
        S = k.shape[1]
        T = self.T
        for j in range(S // T):
            self._freeze_rows(b, k[:, j * T:(j + 1) * T],
                              v[:, j * T:(j + 1) * T])
        rem = S % T
        if rem:
            pad = [(0, 0), (0, T - rem), (0, 0), (0, 0)]
            self.hot_k = self.hot_k.at[b].set(
                jnp.pad(k[:, S - rem:], pad)[0].astype(self.dtype))
            self.hot_v = self.hot_v.at[b].set(
                jnp.pad(v[:, S - rem:], pad)[0].astype(self.dtype))
        else:
            self.hot_k = self.hot_k.at[b].set(0.0)
            self.hot_v = self.hot_v.at[b].set(0.0)
        self.hot_len[b] = rem

    def append(self, k: jnp.ndarray, v: jnp.ndarray) -> None:
        """One decode step's (B, 1, Hkv, D) K/V: scatter every row at its
        slot's hot position, advance active slots, freeze any slot whose
        hot row filled. Inactive slots' scatters land on masked positions
        and are overwritten by their next real token."""
        pos = jnp.asarray(self.hot_len, jnp.int32)
        self.hot_k = _scatter_rows(self.hot_k, k.astype(self.dtype), pos)
        self.hot_v = _scatter_rows(self.hot_v, v.astype(self.dtype), pos)
        self.hot_len = self.hot_len + self.active.astype(np.int32)
        for b in np.nonzero(self.hot_len >= self.T)[0]:
            self._freeze_slot(int(b))

    # -- read path ----------------------------------------------------------

    def invalidate(self) -> None:
        """Drop memoized decoded views (pool storage changed under them);
        the next read decodes through each slot's stores."""
        for b in range(self.max_active):
            self._decoded[b] = [None] * len(self.metas[b])
            self._gf_decoded[b] = [None] * len(self.metas[b])
        self._stack_cache = None
        self._gf_stack_cache = None

    def _decoded_page(self, b: int, j: int) -> tuple[jnp.ndarray, jnp.ndarray]:
        ent = self._decoded[b][j]
        if ent is None:
            kmeta, vmeta = self.metas[b][j]
            p, kk = self.code.p, self.code.k
            if self.pkv.corrected:
                kpg = self.k_stores[b].read_page_corrected(j)
                vpg = self.v_stores[b].read_page_corrected(j)
            else:
                kpg = self.k_stores[b].page(j)
                vpg = self.v_stores[b].page(j)
            ent = (dequantize_tensor(kpg[:, :kk], kmeta, p),
                   dequantize_tensor(vpg[:, :kk], vmeta, p))
            self._decoded[b][j] = ent
        return ent

    def _stacked_page(self, j: int):
        zero = jnp.zeros(self.page_shape, self.dtype)
        ks, vs, valid = [], [], []
        for b in range(self.max_active):
            if j < len(self.metas[b]):
                kd, vd = self._decoded_page(b, j)
                ks.append(kd.astype(self.dtype))
                vs.append(vd.astype(self.dtype))
                valid.append(self.T)
            else:
                ks.append(zero)
                vs.append(zero)
                valid.append(0)
        return (jnp.concatenate(ks), jnp.concatenate(vs),
                jnp.asarray(valid, jnp.int32))

    def pages(self):
        """Yield (k (B,T,Hkv,D), v, valid (B,)) page steps for the streaming
        online-softmax: frozen page j stacks slot b's decoded page j (or a
        masked zero page), the shared hot block rides last with per-slot
        fill. Stacked frozen pages are memoized between freezes."""
        max_pg = max((len(m) for m in self.metas), default=0)
        if self._stack_cache is None or len(self._stack_cache) != max_pg:
            self._stack_cache = [self._stacked_page(j)
                                 for j in range(max_pg)]
        yield from self._stack_cache
        yield (self.hot_k, self.hot_v, jnp.asarray(self.hot_len, jnp.int32))

    # -- fused read path ----------------------------------------------------

    def _gf_page(self, b: int, j: int) -> tuple[jnp.ndarray, jnp.ndarray]:
        """Slot b's corrected GF codeword page j (scan-gated decode through
        the slot's stores, corrections attributed to the owning tenant)."""
        ent = self._gf_decoded[b][j]
        if ent is None:
            ent = (self.k_stores[b].read_page_corrected(j),
                   self.v_stores[b].read_page_corrected(j))
            self._gf_decoded[b][j] = ent
        return ent

    def _fused_inputs(self):
        """Stacked kernel operands: kpages/vpages (NP, B, W, n) int32,
        kscales/vscales (NP, B) f32, valid (NP, B) int32. Page step j
        stacks slot b's page j; slots with fewer pages contribute zero
        pages (valid codewords) with scale 0 and valid 0 — exact no-ops in
        the recurrence. Memoized between freezes."""
        max_pg = max((len(m) for m in self.metas), default=0)
        if (self._gf_stack_cache is None
                or self._gf_stack_cache[0] != max_pg):
            W, n = self.words_per_page, self.code.n
            zero_pg = jnp.zeros((W, n), jnp.int32)
            zero_sc = jnp.zeros((), jnp.float32)
            steps = []
            for j in range(max_pg):
                kps, vps, kss, vss, valid = [], [], [], [], []
                for b in range(self.max_active):
                    if j < len(self.metas[b]):
                        kpg, vpg = self._gf_page(b, j)
                        kmeta, vmeta = self.metas[b][j]
                        kps.append(kpg)
                        vps.append(vpg)
                        kss.append(jnp.asarray(kmeta.scale, jnp.float32))
                        vss.append(jnp.asarray(vmeta.scale, jnp.float32))
                        valid.append(self.T)
                    else:
                        kps.append(zero_pg)
                        vps.append(zero_pg)
                        kss.append(zero_sc)
                        vss.append(zero_sc)
                        valid.append(0)
                steps.append((jnp.stack(kps), jnp.stack(vps),
                              jnp.stack(kss), jnp.stack(vss),
                              jnp.asarray(valid, jnp.int32)))
            # pre-pad the page axis to its np_bucket size with no-op zero
            # pages here (once per freeze), so the per-step
            # attend_protected call pads nothing and issues one dispatch
            from repro.kernels.ops import np_bucket
            B = self.max_active
            NB = np_bucket(max_pg)
            ops_in = (jnp.zeros((NB, B, W, n), jnp.int32),
                      jnp.zeros((NB, B, W, n), jnp.int32),
                      jnp.zeros((NB, B), jnp.float32),
                      jnp.zeros((NB, B), jnp.float32),
                      jnp.zeros((NB, B), jnp.int32))
            if steps:
                ops_in = tuple(
                    z.at[:max_pg].set(jnp.stack([s[i] for s in steps]))
                    for i, z in enumerate(ops_in))
            self._gf_stack_cache = (max_pg, ops_in)
        return self._gf_stack_cache[1]

    def attend(self, q, softcap=0.0):
        """Fused one-kernel batched read (`ops.attend_protected` over the
        per-slot GF page stacks; the shared hot block is applied inside the
        kernel with per-slot fill levels). Streams `pages()` through the
        per-page online-softmax when fusion is off or reads are
        uncorrected."""
        if not (self.pkv.fused and self.pkv.corrected):
            return super().attend(q, softcap)
        kp, vp, ks, vs, valid = self._fused_inputs()
        from repro.kernels import ops
        return ops.attend_protected(
            q, kp, vp, ks, vs, valid, self.hot_k, self.hot_v,
            jnp.asarray(self.hot_len, jnp.int32),
            p=self.code.p, k_info=self.code.k, page_shape=self.page_shape,
            softcap=float(softcap or 0.0), with_hot=True)

    # -- capacity -----------------------------------------------------------

    def freeze_candidates(self, active: np.ndarray) -> int:
        """Pool pages the NEXT step's appends will allocate (2 per slot
        about to fill its hot row) — the engine's preflight input."""
        about = active & (self.hot_len == self.T - 1)
        return 2 * int(about.sum())

    def slot_pages(self, b: int) -> list[int]:
        out: list[int] = []
        for store in (self.k_stores[b], self.v_stores[b]):
            if store is not None:
                out.extend(store.block_table)
        return out


class BatchedDenseKV(KVSource):
    """The unprotected baseline: per-slot dense K/V rows in one
    (max_active, max_seq, Hkv, D) buffer, served through the same
    `KVSource` interface (a single `pages()` step with per-slot valid
    lengths, attended via the default streaming path)."""

    kind = "dense"

    def __init__(self, max_active: int, max_seq: int, hkv: int, dh: int,
                 dtype=CDT):
        self.max_active, self.max_seq = max_active, max_seq
        self.k = jnp.zeros((max_active, max_seq, hkv, dh), dtype)
        self.v = jnp.zeros((max_active, max_seq, hkv, dh), dtype)
        self.len = np.zeros(max_active, np.int32)
        self.dtype = dtype
        self.active = np.zeros(max_active, bool)

    def open_slot(self, b: int, owner=None) -> None:
        self.k = self.k.at[b].set(0.0)
        self.v = self.v.at[b].set(0.0)
        self.len[b] = 0

    def close_slot(self, b: int) -> dict:
        self.len[b] = 0
        return dict.fromkeys(ControllerStats.CORRECTION_KEYS, 0)

    def ingest_slot(self, b: int, k: jnp.ndarray, v: jnp.ndarray) -> None:
        S = k.shape[1]
        pad = [(0, 0), (0, self.max_seq - S), (0, 0), (0, 0)]
        self.k = self.k.at[b].set(jnp.pad(k, pad)[0].astype(self.dtype))
        self.v = self.v.at[b].set(jnp.pad(v, pad)[0].astype(self.dtype))
        self.len[b] = S

    def append(self, k: jnp.ndarray, v: jnp.ndarray) -> None:
        pos = jnp.asarray(self.len, jnp.int32)
        self.k = _scatter_rows(self.k, k.astype(self.dtype), pos)
        self.v = _scatter_rows(self.v, v.astype(self.dtype), pos)
        self.len = self.len + self.active.astype(np.int32)

    def invalidate(self) -> None:
        pass

    def pages(self):
        yield self.k, self.v, jnp.asarray(self.len, jnp.int32)

    def freeze_candidates(self, active: np.ndarray) -> int:
        return 0

    def slot_pages(self, b: int) -> list[int]:
        return []


class EngineCaches:
    """The engine's cache manager: the `view`/`update` surface
    `repro.models.lm._decode_step_protected` drives, one batched KV layer
    per attention position."""

    is_protected_manager = True

    def __init__(self, cfg: ArchConfig,
                 layers: dict[tuple[int, int], Any]):
        self.cfg = cfg
        self.layers = layers

    def view(self, g: int, i: int):
        return self.layers[(g, i)]               # a KVSource

    def update(self, g: int, i: int, new_cache) -> None:
        return None

    def set_active(self, active: np.ndarray) -> None:
        for layer in self.layers.values():
            layer.active = active


@dataclasses.dataclass
class SequenceState:
    """One tenant's request through the engine."""

    tenant: Any
    prompt: np.ndarray                  # (S,) int token ids
    max_new: int
    generated: list[int] = dataclasses.field(default_factory=list)
    status: str = "waiting"             # waiting | active | done
    slot: int | None = None
    replay_idx: int = 0                 # next generated token to feed
    admit_step: int = -1
    preemptions: int = 0
    stats: dict[str, int] = dataclasses.field(
        default_factory=lambda: dict.fromkeys(
            ControllerStats.CORRECTION_KEYS, 0))

    @property
    def done(self) -> bool:
        return self.status == "done"


class ServingEngine:
    """Continuous-batching scheduler over `max_active` slots.

    `submit()` queues sequences; each `step()` admits what fits (pool
    capacity preflighted), runs ONE batched decode step for every active
    slot (greedy sampling), retires finished sequences, and interleaves a
    bounded background scrub of cold pool pages. Preemption (LIFO — the
    youngest sequence yields, vLLM-style) frees blocks when a step's
    freezes would exhaust the pool; preempted sequences readmit by
    re-prefilling their prompt and replaying generated tokens teacher-
    forced, which is bit-exact with never having been evicted."""

    def __init__(self, params, cfg: ArchConfig, *,
                 pkv: ProtectedKVConfig | None = None,
                 pool: ProtectedPagePool | None = None,
                 max_active: int = 16, max_seq: int = 512,
                 protected: bool = True, scrub_every: int = 0,
                 scrub_max_pages: int = 4, scrub_min_age: int = 0):
        self.params, self.cfg = params, cfg
        self.max_active, self.max_seq = max_active, max_seq
        self.protected = protected
        self.scrub_every = scrub_every
        self.scrub_max_pages = scrub_max_pages
        self.scrub_min_age = scrub_min_age
        hkv, dh = cfg.n_kv_heads, cfg.head_dim
        for spec in cfg.group_spec:
            if not (spec.kind == "attn" and not spec.cross
                    and not spec.local_window):
                raise ValueError(
                    "ServingEngine serves global self-attention stacks; "
                    f"layer kind {spec.kind!r} (cross={spec.cross}, "
                    f"window={spec.local_window}) is not batchable here")
        layers: dict[tuple[int, int], Any] = {}
        if protected:
            self.pkv = pkv or ProtectedKVConfig()
            wpu = words_for_tensor((1, self.pkv.page_tokens, hkv, dh),
                                   _code(self.pkv).p, _code(self.pkv).k)
            if pool is None:
                pool = ProtectedPagePool(
                    _code(self.pkv), page_words=wpu,
                    capacity_pages=self._default_capacity(cfg, max_active),
                    n_iters=self.pkv.n_iters, damping=self.pkv.damping,
                    mesh=self.pkv.mesh)
            self.pool = pool
            for g in range(cfg.n_groups):
                for i in range(len(cfg.group_spec)):
                    layers[(g, i)] = BatchedPagedKV(
                        self.pkv, pool, max_active, hkv, dh)
        else:
            self.pkv = pkv
            self.pool = None
            for g in range(cfg.n_groups):
                for i in range(len(cfg.group_spec)):
                    layers[(g, i)] = BatchedDenseKV(max_active, max_seq,
                                                    hkv, dh)
        self.caches = EngineCaches(cfg, layers)
        self.n_stores = 2 * len(layers)      # pool pages per frozen KV page
        self.waiting: deque = deque()
        self.slots: list[SequenceState | None] = [None] * max_active
        self.sequences: list[SequenceState] = []
        self._step_no = 0
        self.scrub_reports: list[dict] = []
        # (max_active, 1, V) device logits of the latest decode step, for
        # callers that check the served numerics (chip_smoke.py)
        self.last_logits = None

    def _default_capacity(self, cfg: ArchConfig, max_active: int) -> int:
        pages_per_seq = -(-self.max_seq // self.pkv.page_tokens)
        n_layers = cfg.n_groups * len(cfg.group_spec)
        return max_active * pages_per_seq * 2 * n_layers

    # -- submission ---------------------------------------------------------

    def submit(self, tenant, prompt, max_new: int) -> SequenceState:
        prompt = np.asarray(prompt, np.int64).reshape(-1)
        if len(prompt) + max_new > self.max_seq:
            raise ValueError(f"prompt {len(prompt)} + max_new {max_new} "
                             f"exceeds max_seq {self.max_seq}")
        seq = SequenceState(tenant=tenant, prompt=prompt, max_new=max_new)
        self.waiting.append(seq)
        self.sequences.append(seq)
        return seq

    # -- scheduling ---------------------------------------------------------

    def _admission_pages(self, seq: SequenceState) -> int:
        if not self.protected:
            return 0
        return (len(seq.prompt) // self.pkv.page_tokens) * self.n_stores

    def _admit(self) -> list[SequenceState]:
        assigns: list[tuple[SequenceState, int]] = []
        reserved: set = set()
        pending_pages = 0
        while self.waiting:
            free = [b for b in range(self.max_active)
                    if self.slots[b] is None and b not in reserved]
            if not free:
                break
            seq = self.waiting[0]
            need = self._admission_pages(seq)
            if (self.protected
                    and pending_pages + need > self.pool.available):
                break
            self.waiting.popleft()
            assigns.append((seq, free[0]))
            reserved.add(free[0])
            pending_pages += need
        # one padded (max_active, S) prefill per distinct prompt length:
        # rows are computation-independent, so a prompt's row is bit-exact
        # whether it shares the batch with 15 other admits or 15 pad rows —
        # and admitting a full engine costs one forward pass, not max_active
        by_len: dict[int, list[tuple[SequenceState, int]]] = {}
        for seq, b in assigns:
            by_len.setdefault(len(seq.prompt), []).append((seq, b))
        for S, group in sorted(by_len.items()):
            self._prefill_group(S, group)
        return [seq for seq, _ in assigns]

    def _prefill_group(self, S: int,
                       group: list[tuple[SequenceState, int]]) -> None:
        from repro.models import lm
        tokens = np.zeros((self.max_active, S), np.int64)
        for j, (seq, _b) in enumerate(group):
            tokens[j] = seq.prompt
        with span("engine.prefill", prompt_len=S, n_seqs=len(group),
                  tenants=[str(s.tenant) for s, _ in group]):
            logits, caches = lm.prefill(self.params, self.cfg,
                                        jnp.asarray(tokens, jnp.int32))
        for j, (seq, b) in enumerate(group):
            for (g, i), layer in self.caches.layers.items():
                entry = caches[f"pos{i}"]
                layer.open_slot(b, owner=seq.tenant)
                layer.ingest_slot(b, entry["k"][g][j:j + 1, :S],
                                  entry["v"][g][j:j + 1, :S])
            if not seq.generated:
                # the prefill's last logit yields the first generated token
                seq.generated.append(int(jnp.argmax(logits[j, -1])))
            seq.replay_idx = 0
            seq.slot = b
            seq.status = "active"
            seq.admit_step = self._step_no
            self.slots[b] = seq
            if len(seq.generated) >= seq.max_new:
                # max_new == 1: the prefill already produced the only token
                self._release_slot(seq)
                seq.status = "done"

    def _release_slot(self, seq: SequenceState) -> None:
        # the ONLY place slot-store counters enter seq.stats: stores are
        # freed by close_slot in the same motion, so a counter is banked
        # exactly once (tenant_stats sums banked + still-live, never both)
        for layer in self.caches.layers.values():
            ControllerStats.add_counts(seq.stats, layer.close_slot(seq.slot))
        self.slots[seq.slot] = None
        seq.slot = None

    def _preempt_one(self) -> int | None:
        """Evict the youngest active sequence (LIFO, vLLM-style): cheapest
        to replay, and the oldest tenants keep streaming. Returns the freed
        slot index."""
        live = [s for s in self.slots if s is not None]
        if len(live) <= 1:
            return None
        victim = max(live, key=lambda s: (s.admit_step, s.slot))
        slot = victim.slot
        self._release_slot(victim)
        victim.status = "waiting"
        victim.preemptions += 1
        victim.replay_idx = 0
        self.waiting.appendleft(victim)   # readmit first
        return slot

    def _preflight(self, active_mask: np.ndarray) -> None:
        if not self.protected:
            return
        while True:
            needed = sum(layer.freeze_candidates(active_mask)
                         for layer in self.caches.layers.values())
            if needed <= self.pool.available:
                return
            slot = self._preempt_one()
            if slot is None:
                raise PoolExhausted(
                    f"next step freezes need {needed} pool pages, only "
                    f"{self.pool.available} free and nothing to preempt — "
                    "grow capacity_pages or lower max_active")
            active_mask[slot] = False

    # -- the step -----------------------------------------------------------

    def step(self) -> dict:
        """One engine tick: admit, preflight capacity, run one batched
        decode step across the active slots, retire finished sequences,
        interleave background scrub. Returns a step report.

        Observability rides along when installed (`repro.obs`): one
        `engine.step` span per tick with admit/decode/scrub child spans,
        step counters/latency into the ambient metrics registry, and the
        RAS estimator drives the scrub schedule — `adaptive_interval`
        shrinks the nominal `scrub_every` period under flag pressure and
        sweeps flag-hot pages first (`prioritize=True`). All of it
        no-ops at one attribute check per pillar when telemetry is off."""
        t_start = time.perf_counter()
        with span("engine.step", step=self._step_no) as sp:
            report = self._step_inner(sp)
        reg = obs_metrics.current()
        if reg.enabled:
            reg.counter("engine_steps", layer="engine").inc()
            reg.counter("engine_tokens", layer="engine").inc(
                report["tokens"])
            reg.counter("engine_retired", layer="engine").inc(
                report["retired"])
            reg.counter("engine_preemptions", layer="engine").inc(
                report["preempted"])
            reg.histogram("engine_step_seconds", layer="engine").observe(
                time.perf_counter() - t_start)
            reg.gauge("engine_active_slots", layer="engine").set(
                report["active"])
        return report

    def _step_inner(self, sp) -> dict:
        from repro.models import lm
        with span("engine.admit"):
            admitted = self._admit()
        active_mask = np.zeros(self.max_active, bool)
        tokens = np.zeros((self.max_active, 1), np.int64)
        pos = np.zeros(self.max_active, np.int64)
        for b, seq in enumerate(self.slots):
            if seq is None:
                continue
            active_mask[b] = True
            tokens[b, 0] = seq.generated[seq.replay_idx]
            pos[b] = len(seq.prompt) + seq.replay_idx
        report = {"step": self._step_no, "admitted": len(admitted),
                  "active": int(active_mask.sum()), "tokens": 0,
                  "retired": 0, "preempted": 0}
        sp.set(active=report["active"], admitted=report["admitted"])
        if not active_mask.any():
            self._step_no += 1
            return report
        pre = sum(s.preemptions for s in self.sequences)
        self._preflight(active_mask)
        report["preempted"] = sum(s.preemptions
                                  for s in self.sequences) - pre
        if report["preempted"]:
            tr = obs_trace.current()
            if tr.enabled:
                tr.instant("engine.preempt", count=report["preempted"])
        if not active_mask.any():
            self._step_no += 1
            return report
        self.caches.set_active(active_mask)
        with span("engine.decode", step=self._step_no,
                  active=report["active"]):
            logits, _ = lm.decode_step(
                self.params, self.cfg, self.caches,
                jnp.asarray(tokens, jnp.int32), jnp.asarray(pos, jnp.int32))
            # keep the sampled tokens on device for now: the argmax host
            # transfer is the step's sync point, and deferring it lets the
            # background scrub's scan/drain dispatches below queue behind
            # the decode step instead of waiting out its completion
            nxt_dev = jnp.argmax(logits[:, -1, :], axis=-1)
            self.last_logits = logits
        self._step_no += 1
        if self.protected:
            self._touch_pages()
            if self.scrub_every and self._due_for_scrub():
                # scrub moves storage TOWARD clean, so memoized decoded
                # views (themselves corrected reads) stay consistent — no
                # invalidation, which is why interleaved scrub stays cheap.
                # (Pages of sequences that retire on this step's token may
                # be swept too — harmless: they are still allocated here,
                # and attribution follows page ownership either way.)
                est = obs_ras.current()
                with span("engine.scrub") as ssp:
                    rep = self.pool.scrub(max_pages=self.scrub_max_pages,
                                          now=self._step_no,
                                          min_age=self.scrub_min_age,
                                          prioritize=est.enabled)
                    ssp.set(pages=rep["pages"],
                            flagged=rep["flagged_words"],
                            repaired=rep["repaired_words"])
                self.scrub_reports.append(rep)
                report["scrubbed_pages"] = rep["pages"]
        with span("engine.sample_sync", step=self._step_no - 1):
            nxt = np.asarray(nxt_dev)
        for b, seq in enumerate(self.slots):
            if seq is None or not active_mask[b]:
                continue
            report["tokens"] += 1
            if seq.replay_idx < len(seq.generated) - 1:
                seq.replay_idx += 1          # teacher-forced replay
            else:
                seq.generated.append(int(nxt[b]))
                seq.replay_idx += 1
            if len(seq.generated) >= seq.max_new:
                self._release_slot(seq)
                seq.status = "done"
                report["retired"] += 1
        return report

    def _due_for_scrub(self) -> bool:
        """Fixed `scrub_every` cadence, unless an ambient RAS estimator is
        installed — then the period is `adaptive_interval(scrub_every)`:
        shorter while pages flag above the estimator's target rate, longer
        when the pool is quiet."""
        est = obs_ras.current()
        interval = self.scrub_every
        if est.enabled:
            interval = max(1, est.adaptive_interval(self.scrub_every))
        return self._step_no % interval == 0

    def _touch_pages(self) -> None:
        for b, seq in enumerate(self.slots):
            if seq is None:
                continue
            for layer in self.caches.layers.values():
                for pid in layer.slot_pages(b):
                    self.pool.touch(pid, self._step_no)

    def _invalidate_all(self) -> None:
        for layer in self.caches.layers.values():
            layer.invalidate()

    def run(self, max_steps: int = 100000) -> dict[Any, list[int]]:
        """Step until every submitted sequence finishes. Returns
        {tenant: generated tokens}."""
        steps = 0
        while (self.waiting or any(s is not None for s in self.slots)):
            if steps >= max_steps:
                raise RuntimeError(f"run() exceeded {max_steps} steps")
            self.step()
            steps += 1
        return {s.tenant: list(s.generated) for s in self.sequences}

    # -- fault injection / stats --------------------------------------------

    def inject(self, channel, key, *, tenants=None, **kw) -> int:
        """Corrupt the shared pool mid-serving (optionally only pages owned
        by `tenants`) and invalidate decoded views, so the next step's
        reads run through the decoder."""
        if not self.protected:
            return 0
        changed = self.pool.inject(channel, key, owners=tenants, **kw)
        self._invalidate_all()
        return changed

    @staticmethod
    def _slot_stores(layer, b: int):
        """The live `PooledStore`s behind slot `b` of one KV layer (empty
        for unprotected/dense layers)."""
        for name in ("k_stores", "v_stores"):
            stores = getattr(layer, name, None)
            if stores is not None and stores[b] is not None:
                yield stores[b]

    def tenant_stats(self, tenant) -> dict[str, int]:
        """Aggregated correction accounting for one tenant: banked counters
        from retired/preempted slots, live slot stores, and the pool's
        per-owner scrub attribution."""
        out = dict.fromkeys(
            ControllerStats.CORRECTION_KEYS + ("scrub_flagged",
                                               "scrub_repaired"), 0)
        for seq in self.sequences:
            if seq.tenant != tenant:
                continue
            # banked counters (stores freed on slot close — disjoint from
            # the live-store sums below by construction, see _release_slot)
            ControllerStats.add_counts(out, seq.stats)
            if seq.slot is not None:
                for layer in self.caches.layers.values():
                    for store in self._slot_stores(layer, seq.slot):
                        ControllerStats.add_counts(out, store.stats)
        if self.protected:
            ent = self.pool.scrub_by_owner.get(tenant)
            if ent:
                out["scrub_flagged"] = ent["flagged_words"]
                out["scrub_repaired"] = ent["repaired_words"]
        return out

    def publish_metrics(self, registry=None) -> None:
        """Export the engine's current accounting into a metrics registry
        (the ambient one by default): per-tenant correction triples and
        scrub attribution as gauges (idempotent across repeated publishes),
        plus the pool's `ControllerStats`. Benchmarks call this right
        before `registry.snapshot()` so per-tenant corrected counts land
        in the exported artifact."""
        reg = obs_metrics.current() if registry is None else registry
        if not getattr(reg, "enabled", False):
            return
        tenants = {}
        for s in self.sequences:
            tenants.setdefault(str(s.tenant), s.tenant)
        for label in sorted(tenants):
            for k, v in self.tenant_stats(tenants[label]).items():
                reg.gauge(f"tenant_{k}", layer="engine",
                          tenant=label).set(v)
        if self.protected:
            self.pool.stats.publish(reg, layer="pool")
            reg.gauge("pool_allocated", layer="pool").set(
                self.pool.n_allocated)
            reg.gauge("pool_available", layer="pool").set(
                self.pool.available)

    def stats(self) -> dict:
        live = sum(s is not None for s in self.slots)
        out = {"step": self._step_no, "active": live,
               "waiting": len(self.waiting),
               "done": sum(s.done for s in self.sequences),
               "preemptions": sum(s.preemptions for s in self.sequences)}
        if self.protected:
            out["pool_allocated"] = self.pool.n_allocated
            out["pool_available"] = self.pool.available
            out["scrub_rounds"] = self.pool.stats.scrub_rounds
        return out


def _code(pkv: ProtectedKVConfig):
    from repro.core import get_code
    return get_code(pkv.code_name)
