"""`PagedProtectedStore`: the device-resident protected-store backend.

Where `repro.memory.array.ProtectedMemoryArray` (the host packing backend)
holds numpy codewords and decodes whole tensors synchronously — right for
checkpoints — this backend keeps storage as fixed-shape **(page_words, n)
GF-level pages living as jax arrays**, so protection can sit under live
workloads:

- **encode on device** — appended info words run through
  `repro.kernels.ops.encode_words` (the Pallas `gf_matmul` MXU path with the
  mod-p fused epilogue); one cached (page_words, k) executable serves every
  append, and pages never round-trip through the host;
- **scan on device** — syndrome flagging via the fused `scan_syndromes`
  kernel (only the (page_words,) masks leave the device); a scrub scans a
  group of up to `SCAN_GROUP` pages per launch;
- **streaming corrected reads** — `iter_corrected()` walks the pages through
  `repro.core.protected.decode_pipelined`: page *i+1*'s decode is dispatched
  before page *i* is yielded, so decode latency hides behind the consumer
  (attention, in the protected KV-serving path). Clean pages (no flags) skip
  the decoder entirely.

With `mesh` set, pages are shard_map'd across the local devices row-wise
(`decode_sharded` / `scan_syndromes_sharded`), alongside the batch axis the
rest of the stack already shards.

`quantize_tensor` / `dequantize_tensor` are the jittable float<->GF bridges
used by the protected KV cache (`repro.models.kv`): absmax int8 quantization,
then base-p symbolization (`repro.memory.packing`, shared with the host
backend so device pages and host checkpoints interoperate bit-exactly).
"""
from __future__ import annotations

import dataclasses
import math
from collections.abc import Iterator

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import get_code
from repro.core.construction import LDPCCode
from repro.core.decode import decode_integers
from repro.core.protected import decode_pipelined, np_prod_mesh
from repro.obs import metrics as obs_metrics
from repro.obs import ras as obs_ras
from repro.obs.trace import span

from .channel import Channel, apply_faults
from .controller import ControllerStats
from .packing import digits_per_byte, symbolize_u8, desymbolize_u8

__all__ = ["PagedProtectedStore", "QuantizedTensor", "quantize_tensor",
           "dequantize_tensor", "words_for_tensor"]

# Pages a coalesced scrub scans per launch, a power of two: 256 pages of 256
# words is 64 Ki words, a 64 MiB int8 operand for the fused scan.
SCAN_GROUP = 256


# ---------------------------------------------------------------------------
# float tensor <-> info words (jittable; the KV-cache quantization bridge)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class QuantizedTensor:
    """Metadata needed to reassemble a tensor from its info words."""

    shape: tuple
    dtype: str
    scale: jnp.ndarray          # () float32 absmax scale
    n_words: int                # info words the tensor occupies


def words_for_tensor(shape, p: int, k: int) -> int:
    """Info words an int8-quantized tensor of `shape` packs into."""
    numel = int(np.prod(shape)) if shape else 1
    return math.ceil(numel * digits_per_byte(p) / k) if numel else 0


def quantize_tensor(x: jnp.ndarray, p: int, k: int
                    ) -> tuple[jnp.ndarray, QuantizedTensor]:
    """absmax-int8 quantize + symbolize + pack: float tensor -> ((m, k) info
    words in [0, p), QuantizedTensor meta). Pure jnp (a handful of
    elementwise dispatches — the encode/decode executables dominate the
    page path). Padding digits are zero (they decode to bytes that are
    sliced off)."""
    shape, dtype = tuple(x.shape), str(x.dtype)
    xf = x.astype(jnp.float32).reshape(-1)
    absmax = jnp.max(jnp.abs(xf)) if xf.size else jnp.float32(0)
    scale = jnp.maximum(absmax, 1e-30) / 127.0
    q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int32)
    u8 = q + 128                                   # [1, 255] byte values
    digits = symbolize_u8(u8, p).reshape(-1)       # (numel * D,)
    k = int(k)
    m = words_for_tensor(shape, p, k)
    pad = m * k - digits.shape[0]
    if pad:
        digits = jnp.concatenate([digits, jnp.zeros(pad, digits.dtype)])
    return digits.reshape(m, k), QuantizedTensor(shape, dtype, scale, m)


def dequantize_tensor(words: jnp.ndarray, meta: QuantizedTensor,
                      p: int) -> jnp.ndarray:
    """Inverse bridge: (m, k) decoded info words -> tensor of `meta.shape`.
    Corrupted-but-uncorrected symbols degrade to wrong values, never
    crashes (digits are clipped into the field)."""
    numel = int(np.prod(meta.shape)) if meta.shape else 1
    D = digits_per_byte(p)
    digits = words.reshape(-1)[:numel * D].reshape(numel, D)
    u8 = desymbolize_u8(digits, p)
    q = u8.astype(jnp.float32) - 128.0
    out = (q * meta.scale).astype(meta.dtype)
    return out.reshape(meta.shape)


# ---------------------------------------------------------------------------
# the device-resident paged store
# ---------------------------------------------------------------------------


class PagedProtectedStore:
    """Fixed-shape (page_words, n) GF-level pages as jax arrays, with device
    encode, per-page syndrome flagging, and pipelined corrected reads."""

    def __init__(self, code: str | LDPCCode = "wl1024_r08", *,
                 page_words: int = 256, mesh=None, n_iters: int = 10,
                 damping: float = 0.3, llv_scale: float = 4.0,
                 llv_mode: str = "manhattan", key: int = 0,
                 policy=None):
        self.code = get_code(code) if isinstance(code, str) else code
        # The device encode/scan executables accumulate int32: every
        # dot-product term is a product of two symbols in [0, p), so the
        # per-word sum is bounded by n*(p-1)^2 and must stay below 2^31.
        # Codes past that belong on MemoryController's exact int64 host
        # path — reject them here rather than wrap silently in the kernel.
        if self.code.n * (self.code.p - 1) ** 2 >= 2 ** 31:
            raise ValueError(
                f"code n={self.code.n} p={self.code.p} exceeds the int32 "
                "kernel accumulator bound n*(p-1)^2 < 2^31; use "
                "MemoryController's exact host scan for this code")
        from repro.kernels.ops import MAX_KERNEL_FIELD
        if self.code.p > MAX_KERNEL_FIELD:
            raise ValueError(
                f"GF({self.code.p}) symbols do not fit the kernels' int8 "
                f"operands (p <= {MAX_KERNEL_FIELD}); use MemoryController's "
                "exact host scan for this code")
        # Backend selection is one KernelPolicy (repro.kernels.backend):
        # None defers to the ambient policy at executable-build time —
        # "auto" compiles the Pallas kernels natively on TPU and routes to
        # the jitted jnp oracles elsewhere (bit-identical by the kernel
        # parity tests); interpret-mode is the CPU correctness path.
        if policy is not None:
            from repro.kernels.backend import _as_policy
            policy = _as_policy(policy)
        self.policy = policy
        if page_words <= 0:
            raise ValueError(f"page_words must be positive, got {page_words}")
        if mesh is not None:
            mesh_size = np_prod_mesh(mesh)
            if page_words % mesh_size != 0:
                raise ValueError(
                    f"page_words={page_words} is not a multiple of the mesh "
                    f"size {mesh_size}; pages are shard_map'd row-wise, so "
                    "pick a page size divisible by the device count")
        self.page_words = page_words
        self.mesh = mesh
        self.n_iters = n_iters
        self.damping = damping
        self.llv_scale = llv_scale
        self.llv_mode = llv_mode
        self._pages: list = []            # [(page_words, n) int32 jax arrays]
        self._new_page = lambda: jnp.zeros((page_words, self.code.n),
                                           jnp.int32)
        if mesh is not None:
            from repro.distributed.sharding import shard_page
            base = self._new_page
            self._new_page = lambda: shard_page(base(), mesh)
        self._n_words = 0                 # valid words across pages
        self._key = jax.random.PRNGKey(key)
        self._injections = 0
        self._encode_fn = None
        self._scan_fn = None
        self._group_scan_fns: dict = {}
        self._decode_fn = None
        self._repair_q = None
        # read/scrub correction accounting (per-store, so a serving layer can
        # attribute corrections to the tenant that owns the store)
        self.stats = ControllerStats()

    # -- introspection ------------------------------------------------------

    @property
    def n_words(self) -> int:
        return self._n_words

    @property
    def n_pages(self) -> int:
        return len(self._pages)

    @property
    def n_cells(self) -> int:
        return self._n_words * self.code.n

    def page(self, i: int) -> jnp.ndarray:
        return self._pages[i]

    # -- storage indirection -------------------------------------------------
    # All page reads/writes go through these four primitives. The standalone
    # store owns a plain list of jax arrays; `repro.memory.pool.PooledStore`
    # overrides them to address a shared ref-counted page pool through a
    # per-tenant block table instead.

    def _set_page(self, i: int, page: jnp.ndarray) -> None:
        self._pages[i] = page

    def _append_page(self) -> None:
        """Grow storage by one zeroed page."""
        self._pages.append(self._new_page())

    def _iter_pages(self) -> Iterator[jnp.ndarray]:
        for i in range(self.n_pages):
            yield self.page(i)

    def free(self) -> None:
        """Release all storage (pool-backed stores return their pages to the
        shared free list; the standalone store just drops them)."""
        self._pages.clear()
        self._n_words = 0

    # -- cached executables -------------------------------------------------

    def _mode(self) -> str:
        """Resolved kernel mode: the store's pinned policy, else the
        ambient one — sampled when a cached executable is (re)built."""
        from repro.kernels.backend import current_policy
        return (self.policy or current_policy()).resolve()

    def _use_kernels(self) -> bool:
        return self._mode() != "ref"

    def _encoder(self):
        """One cached (page_words, k) device-encode executable: the Pallas
        `encode_words` MXU path on TPU, its jitted jnp oracle elsewhere.
        The resolved mode is baked in at build time (the interpret flag is
        passed explicitly so a later ambient-policy change can't silently
        retarget a cached trace)."""
        if self._encode_fn is None:
            P = jnp.asarray(self.code.P, jnp.int32)
            p = self.code.p
            mode = self._mode()
            if mode != "ref":
                from repro.kernels.ops import encode_words
                interp = mode == "interpret"
                self._encode_fn = jax.jit(
                    lambda u: encode_words(u, P, p, interpret=interp))
            else:
                from repro.kernels.ref import encode_words_ref
                self._encode_fn = jax.jit(
                    lambda u: encode_words_ref(u, P, p))
        return self._encode_fn

    def _row_scan(self):
        """(rows, n) -> (rows,) flags, unjitted: the fused Pallas scan, or
        its jnp oracle under the `ref` policy (mode resolved now)."""
        ht = jnp.asarray(self.code.H.T, jnp.int32)
        p = self.code.p
        mode = self._mode()
        if mode != "ref":
            from repro.kernels.ops import scan_syndromes
            interp = mode == "interpret"
            return lambda y: scan_syndromes(y, ht, p, interpret=interp)
        from repro.kernels.ref import scan_syndromes_ref
        return lambda y: scan_syndromes_ref(y, ht, p)

    def _scanner(self):
        """One cached (page_words, n) syndrome-scan executable (fused Pallas
        kernel on TPU, jnp oracle elsewhere; sharded over `mesh` when
        given)."""
        if self._scan_fn is None:
            if self.mesh is not None:
                from repro.distributed.sharding import scan_syndromes_sharded
                code, mesh = self.code, self.mesh
                self._scan_fn = jax.jit(
                    lambda y: scan_syndromes_sharded(code, y, mesh=mesh))
            else:
                self._scan_fn = jax.jit(self._row_scan())
        return self._scan_fn

    def _group_scanner(self, g: int):
        """One cached executable per group size `g`: a tuple of `g`
        (page_words, n) pages -> (g, page_words) bool masks, from one scan
        of the pages stacked row-wise (the kernel's int8 cast fuses with
        the concatenate)."""
        fn = self._group_scan_fns.get(g)
        if fn is None:
            scan = self._row_scan()
            fn = self._group_scan_fns[g] = jax.jit(
                lambda pages: scan(jnp.concatenate(pages)).reshape(g, -1))
        return fn

    def _decoder(self):
        """One cached (page_words, n) decode executable (sharded over
        `mesh` when given)."""
        if self._decode_fn is None:
            code = self.code
            kw = dict(n_iters=self.n_iters, damping=self.damping,
                      llv_scale=self.llv_scale, llv_mode=self.llv_mode,
                      early_exit=True)
            if self.mesh is not None:
                from repro.distributed.sharding import decode_sharded
                mesh = self.mesh
                self._decode_fn = jax.jit(
                    lambda y: decode_sharded(code, y, mesh=mesh, **kw))
            else:
                self._decode_fn = jax.jit(
                    lambda y: decode_integers(code, y, **kw))
        return self._decode_fn

    def _repair_queue(self):
        """The coalescing repair queue this store's scrubs drain through
        (cross-page flagged-row batching; see `repro.memory.repair`).
        `PooledStore` delegates to the pool template's queue, so every
        tenant of a pool shares one queue — and one coalesced drain.

        Serving-facing stores pin a SINGLE decode bucket
        (`min_bucket=page_words`): a drain here is at most a few pages'
        sparse flags, so the bucket ladder could only trade pad rows
        (microseconds) for extra jit compiles (~seconds each) that land as
        p99 spikes inside serving steps. The controller's scrub-daemon
        queue keeps the full power-of-two ladder, where sweep shapes are
        stable and bucketing pays."""
        if self._repair_q is None:
            from .repair import RepairQueue
            self._repair_q = RepairQueue(
                self.code, chunk_size=self.page_words,
                min_bucket=self.page_words,
                n_iters=self.n_iters, damping=self.damping,
                llv_scale=self.llv_scale, llv_mode=self.llv_mode)
        return self._repair_q

    # -- write path ---------------------------------------------------------

    def _encode_rows(self, u: jnp.ndarray) -> jnp.ndarray:
        """Encode (b, k) info rows through the fixed-shape executable."""
        b = u.shape[0]
        if b < self.page_words:
            u = jnp.concatenate(
                [u, jnp.zeros((self.page_words - b, u.shape[1]), u.dtype)])
        return self._encoder()(u.astype(jnp.int32))[:b]

    def append_words(self, u) -> tuple[int, int]:
        """Append (m, k) info words (field symbols in [0, p)): encode on
        device and pack into pages. Returns the occupied word range
        [start, start + m). A partially-filled trailing page is padded with
        all-zero words (valid codewords — scan-neutral) and topped up by the
        next append."""
        u = jnp.asarray(u)
        if u.ndim != 2 or u.shape[1] != self.code.k:
            raise ValueError(f"expected (m, {self.code.k}) info words, got "
                             f"{tuple(u.shape)}")
        m = u.shape[0]
        start = self._n_words
        pw = self.page_words
        done = 0
        while done < m:
            slot = self._n_words % pw
            if slot == 0:
                self._append_page()
            take = min(m - done, pw - slot)
            enc = self._encode_rows(u[done:done + take])
            last = self.n_pages - 1
            self._set_page(last, jax.lax.dynamic_update_slice(
                self.page(last), enc, (slot, 0)))
            done += take
            self._n_words += take
        self.stats.writes += 1
        self.stats.words_written += m
        return start, start + m

    def append_encoded(self, enc) -> tuple[int, int]:
        """Adopt already-encoded (m, n) codewords (e.g. host-encoded
        checkpoint pages from `ProtectedMemoryArray.stored`) without
        re-encoding — the backend-interop path."""
        enc = jnp.asarray(enc, jnp.int32)
        if enc.ndim != 2 or enc.shape[1] != self.code.n:
            raise ValueError(f"expected (m, {self.code.n}) codewords, got "
                             f"{tuple(enc.shape)}")
        m = enc.shape[0]
        start = self._n_words
        pw = self.page_words
        done = 0
        while done < m:
            slot = self._n_words % pw
            if slot == 0:
                self._append_page()
            take = min(m - done, pw - slot)
            last = self.n_pages - 1
            self._set_page(last, jax.lax.dynamic_update_slice(
                self.page(last), enc[done:done + take], (slot, 0)))
            done += take
            self._n_words += take
        self.stats.writes += 1
        self.stats.words_written += m
        return start, start + m

    def export_words(self) -> np.ndarray:
        """All valid stored codewords as one host (n_words, n) int8 array
        (checkpoint hand-off to the host backend)."""
        if not self.n_pages:
            return np.zeros((0, self.code.n), np.int8)
        # one transfer for the whole store, not one per page
        flat = np.concatenate(jax.device_get(list(self._iter_pages())))
        return flat[:self._n_words].astype(np.int8)

    # -- fault injection ----------------------------------------------------

    def inject(self, channel: Channel,
               key: int | jax.Array | None = None, *, t: float = 0.0,
               n_reads: int = 0, exact: int = 0) -> int:
        """Corrupt the stored pages in place through a level-domain channel
        model (device-side). Returns the number of cells changed. Pad rows
        of the trailing page are corrupted too — they are storage like any
        other row, and the scan/decode path treats their errors normally.
        `exact=m` corrupts exactly m cells of every word instead."""
        if channel.domain != "level":
            raise ValueError(f"{type(channel).__name__} is an integer-domain "
                             "channel; stored cells need a level-domain one")
        if channel.p != self.code.p:
            raise ValueError(f"channel alphabet {channel.p} != "
                             f"GF({self.code.p})")
        if key is None:
            key = jax.random.fold_in(self._key, self._injections)
        elif isinstance(key, int):
            key = jax.random.PRNGKey(key)
        self._injections += 1
        changed = 0
        for i in range(self.n_pages):
            page = self.page(i)
            k = jax.random.fold_in(key, i)
            new = apply_faults(channel, k, page, exact, t=t, n_reads=n_reads)
            new = new.astype(jnp.int32)
            changed += int(jnp.sum(new != page))
            self._set_page(i, new)
        return changed

    # -- read path ----------------------------------------------------------

    def scan_flags(self) -> np.ndarray:
        """(n_words,) bool — per-word nonzero-syndrome flags via the fused
        device scan, streamed page by page through one executable."""
        if not self.n_pages:
            return np.zeros(0, bool)
        fn = self._scanner()
        # dispatch every page's scan, then pull all masks in one sync
        flags = np.concatenate(
            jax.device_get([fn(pg) for pg in self._iter_pages()]))
        return flags[:self._n_words]

    def iter_corrected(self, *, scan_first: bool = True,
                       depth: int = 1) -> Iterator[jnp.ndarray]:
        """Yield (page_words, n) corrected symbol pages in storage order,
        double-buffered: page i+1's scan/decode is dispatched before page i
        is yielded, so decode overlaps the consumer. With `scan_first`,
        clean pages bypass the decoder entirely (the serving fast path:
        scan is one fused matmul; FBP runs only where the scan flags)."""
        if depth < 1:
            raise ValueError(f"depth must be >= 1, got {depth}")
        scan = self._scanner() if scan_first else None
        decode = self._decoder()

        def dispatch(page):
            if scan is not None:
                nf = int(np.asarray(scan(page)).sum())
                if not nf:
                    return page                   # clean: levels ARE symbols
                self.stats.detected += nf
            _y, res = decode(page)                # async dispatch
            return res.symbols

        pending = []
        for page in self._iter_pages():
            self.stats.reads += 1
            self.stats.words_read += self.page_words
            pending.append(dispatch(page))
            if len(pending) > depth:
                yield pending.pop(0)
        yield from pending

    def read_page_corrected(self, i: int) -> jnp.ndarray:
        """Scan-gated synchronous corrected read of page `i`, with full
        correction accounting on `self.stats` (detected / corrected /
        uncorrectable). The per-page primitive the serving engine uses to
        attribute corrections to the tenant owning this store."""
        page = self.page(i)
        self.stats.reads += 1
        self.stats.words_read += self.page_words
        flags = np.asarray(self._scanner()(page))
        nf = int(flags.sum())
        est = obs_ras.current()
        owner = getattr(self, "owner", None)
        region = str(owner) if owner is not None else ""
        if est.enabled:
            est.observe_scan(nf, self.page_words, n_symbols=self.code.n,
                             region=region)
        if not nf:
            return page
        self.stats.detected += nf
        _y, res = self._decoder()(page)
        bad = int((flags & np.asarray(res.detect_fail)).sum())
        self.stats.uncorrectable += bad
        self.stats.corrected += nf - bad
        reg = obs_metrics.current()
        if reg.enabled:
            lab = {"layer": "paged", "tenant": region,
                   "code": f"gf{self.code.p}n{self.code.n}"}
            reg.counter("mem_detected", **lab).inc(nf)
            reg.counter("mem_corrected", **lab).inc(nf - bad)
            reg.counter("mem_uncorrectable", **lab).inc(bad)
        if est.enabled:
            iters = getattr(res, "iterations", None)
            if iters is not None:
                est.observe_decode(iters, self.n_iters,
                                   detect_fail=res.detect_fail,
                                   region=region)
        return res.symbols

    def read_corrected(self) -> jnp.ndarray:
        """Synchronous whole-store corrected read: every page decoded and
        stacked to (n_words, n) symbols. The baseline the pipelined read is
        benchmarked against."""
        if not self.n_pages:
            return jnp.zeros((0, self.code.n), jnp.int32)
        decode = self._decoder()
        outs = [decode(pg)[1].symbols for pg in self._iter_pages()]
        return jnp.concatenate(outs)[:self._n_words]

    def read_words(self, start: int, stop: int, *,
                   corrected: bool = True) -> jnp.ndarray:
        """Gather stored words [start, stop) across pages (corrected via the
        per-page scan+decode route, or raw levels)."""
        if not 0 <= start <= stop <= self._n_words:
            raise ValueError(f"word range [{start}, {stop}) outside "
                             f"[0, {self._n_words})")
        if start == stop:
            return jnp.zeros((0, self.code.n), jnp.int32)
        pw = self.page_words
        out = []
        for pi in range(start // pw, (stop - 1) // pw + 1):
            page = (self.read_page_corrected(pi) if corrected
                    else self.page(pi))
            lo = max(start - pi * pw, 0)
            hi = min(stop - pi * pw, pw)
            out.append(page[lo:hi])
        return jnp.concatenate(out)

    def read_info(self, start: int, stop: int, *,
                  corrected: bool = True) -> jnp.ndarray:
        """Like `read_words` but sliced to the (m, k) info symbols — the
        shape `dequantize_tensor` consumes."""
        return self.read_words(start, stop, corrected=corrected)[:, :self.code.k]

    def decode_stream(self, **kw) -> Iterator:
        """The raw `(y_corrected, DecodeResult)` pipeline over the stored
        pages (see `repro.core.protected.decode_pipelined`) for consumers
        that need decode metadata (detect_fail, iterations) per page."""
        kw.setdefault("chunk_size", self.page_words)
        kw.setdefault("n_iters", self.n_iters)
        kw.setdefault("damping", self.damping)
        kw.setdefault("llv_scale", self.llv_scale)
        kw.setdefault("llv_mode", self.llv_mode)
        kw.setdefault("mesh", self.mesh)
        return decode_pipelined(self.code, self._iter_pages(), **kw)

    def scrub(self, pages=None, *, coalesce: bool = True) -> dict:
        """Sweep the pages: scan, repair flagged words, write back
        (device-side). `pages` optionally restricts the sweep to a subset of
        page indices (the engine's cold-page background scrub). Returns
        {pages, flagged_words, repaired_words}.

        `coalesce=True` (default) runs the repair pipeline: the pages are
        scanned a group at a time (`SCAN_GROUP` pages per launch), every
        group is launched before any mask is pulled (one sync for the
        sweep), flagged rows are gathered on device and coalesced across
        pages on the `RepairQueue`, and one bucketed drain repairs them —
        sparse flags pay a bucket-sized FBP instead of a whole-page one.
        `coalesce=False` keeps the per-page scan→whole-page-decode baseline
        (bit-identical repairs; FBP is row-independent)."""
        idxs = list(range(self.n_pages) if pages is None else pages)
        with span("scrub.sweep"):
            if coalesce:
                report = self._scrub_coalesced(idxs)
            else:
                report = self._scrub_baseline(idxs)
            self.stats.scrub_rounds += 1
            self.stats.scrub_words += report["pages"] * self.page_words
            self.stats.scrub_corrected += report["repaired_words"]
            self.stats.scrub_uncorrectable += (report["flagged_words"]
                                               - report["repaired_words"])
        return report

    def _scrub_baseline(self, idxs: list[int]) -> dict:
        """Per-page sweep: sync each page's flag count, decode the whole
        page when any row flags (the pre-pipeline behavior)."""
        scan, decode = self._scanner(), self._decoder()
        flagged_words = repaired = swept = 0
        for i in idxs:
            page = self.page(i)
            swept += 1
            flags = scan(page)
            nf = int(jnp.sum(flags))
            if not nf:
                continue
            flagged_words += nf
            _y, res = decode(page)
            good = flags & ~res.detect_fail
            self._set_page(i, jnp.where(good[:, None], res.symbols, page))
            repaired += int(jnp.sum(good))
        return {"pages": swept, "flagged_words": flagged_words,
                "repaired_words": repaired, "coalesced": False}

    def _scan_masks(self, pages: list) -> np.ndarray:
        """(len(pages), page_words) bool scan masks of `pages`: every
        group's scan is launched before one `device_get` pulls every mask.
        A group holds `SCAN_GROUP` pages; the last one is padded up to a
        power of two with repeats of its first page, whose masks are
        dropped, so sweeps build at most log2(SCAN_GROUP) + 1 shapes
        whatever their page counts. A `mesh` store launches its sharded
        scan page by page."""
        m, pw = len(pages), self.page_words
        g_max = 1 if self.mesh is not None else SCAN_GROUP
        groups = [pages[lo:lo + g_max] for lo in range(0, m, g_max)]
        with span("scrub.scan_dispatch", dispatches=len(groups), pages=m):
            if self.mesh is not None:
                scan = self._scanner()
                launched = [scan(grp[0]) for grp in groups]
            else:
                launched = []
                for grp in groups:
                    g = 1 << (len(grp) - 1).bit_length()
                    launched.append(self._group_scanner(g)(
                        tuple(grp + grp[:1] * (g - len(grp)))))
        with span("scrub.mask_pull") as sp:
            pulled = jax.device_get(launched)
            sp.set(bytes=sum(a.nbytes for a in launched))
        return np.concatenate([a.reshape(-1, pw) for a in pulled])[:m]

    def _scrub_coalesced(self, idxs: list[int]) -> dict:
        """Pipelined sweep: launch the scans a group of pages at a time,
        one mask sync, pull the flagged pages whole in a second batched
        sync, one coalesced bucketed drain. Rows are sliced and repaired
        on host page copies so every device op stays group-, page- or
        bucket-shaped — per-flag-count gathers/scatters would recompile on
        every new count. The phases are `scrub.*` spans whose args count
        what each moved."""
        if not idxs:
            return {"pages": 0, "flagged_words": 0, "repaired_words": 0,
                    "coalesced": True}
        masks = self._scan_masks([self.page(i) for i in idxs])
        queue = self._repair_queue()
        owner = getattr(self, "owner", None)
        with span("scrub.page_pull") as sp:
            flagged = [(i, rows) for i, mask in zip(idxs, masks, strict=True)
                       if (rows := np.flatnonzero(mask)).size]
            pages = jax.device_get([self.page(i) for i, _ in flagged])
            flagged_words = 0
            for (i, rows), arr in zip(flagged, pages, strict=True):
                arr = np.array(arr)    # device_get views can be read-only
                flagged_words += int(rows.size)

                def writeback(syms, ok, i=i, rows=rows, arr=arr):
                    good = rows[ok]
                    if not good.size:
                        return 0
                    arr[good] = syms[ok].astype(arr.dtype)
                    self._set_page(i, jnp.asarray(arr, jnp.int32))
                    return arr.nbytes

                queue.enqueue(arr[rows], writeback, owner=owner,
                              provenance=("store", i, rows))
            sp.set(bytes=len(pages) * self.page_words * self.code.n * 4)
        rep = queue.drain()
        return {"pages": len(idxs), "flagged_words": flagged_words,
                "repaired_words": rep["repaired"], "coalesced": True,
                "drain": {k: rep[k] for k in (
                    "entries", "words", "repaired", "failed", "pad_rows",
                    "dispatch_rows", "pad_waste")}}
