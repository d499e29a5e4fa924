"""Pluggable memory-controller policies for NB-LDPC-protected storage.

Modeled on the classic ECC-memory-controller taxonomy (basic / write-back /
refresh):

- **basic** — correct read responses, never touch the stored words; latent
  errors accumulate in storage until they exceed the code's strength.
- **writeback** — additionally rewrite every corrected word back into
  storage on read, so each read also repairs (read-triggered refresh).
- **scrub** — writeback plus a periodic background sweep over the whole
  array: syndromes are scanned for every stored word, flagged words are
  batch-decoded (sharded across local devices via
  `repro.distributed.sharding.decode_sharded` when more than one is
  visible) and repaired in place. `interval` counts read/write operations
  between automatic sweeps; `scrub()` can also be called explicitly.

All policies share the same read path: a cheap syndrome scan over the
stored words, then the iterative decoder runs ONLY on flagged words,
gathered into fixed-size chunks so one jitted executable serves every read
(the same trick as `repro.core.protected.decode_stream`). Per-policy
counters (detected / corrected / uncorrectable / writebacks / scrub
bandwidth) live in `ControllerStats`.

The scan itself has two routes, selected by the controller's pinned
`KernelPolicy` (`policy=`) or the ambient `repro.kernels.use_policy` —
`ref` mode runs the host scan, every other mode the device scan:

- **host** — float32 BLAS matmul (exact while n·(p−1)² < 2²⁴; beyond that
  it degrades to an exact-but-slower int64 path automatically);
- **device** — the fused Pallas `repro.kernels.ops.scan_syndromes` kernel:
  mod-p + any-reduce fused into the matmul epilogue, so only the (B,) flag
  mask crosses back to the host, never the syndrome matrix. Pages are
  streamed through ONE cached fixed-shape executable (`scan_block` rows)
  and fanned across local devices via the `decode_sharded` mesh when more
  than one is visible. The default `auto` mode compiles on TPU and
  interprets elsewhere (a correctness path, not a fast path, on CPU).

Every read and sweep also feeds the ambient observability layer
(`repro.obs`): correction counters into the metrics registry, per-page
scan-flag rates and decoder iteration vectors into the RAS estimator —
both free no-ops unless `use_metrics` / `use_estimator` is active.

Scrubbing is **paged**: `scrub(page_words=...)` streams fixed-size pages of
stored words (`scrub_pages` accepts any iterator of writable (b, n) row
views) so arrays larger than device memory scrub incrementally; repairs are
written back through the page views and per-page stats ride in the report.
"""
from __future__ import annotations

import dataclasses
import time
from collections.abc import Iterable, Iterator

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.construction import LDPCCode
from repro.core.decode import decode_integers
from repro.obs import metrics as obs_metrics
from repro.obs import ras as obs_ras

from .repair import RepairQueue

__all__ = ["ControllerStats", "MemoryController", "WritebackController",
           "ScrubController", "make_controller"]

# per-page entries kept in a sweep report; totals keep accumulating past
# this, so a million-page archive sweep stays one-page-resident instead of
# holding millions of stat dicts
MAX_PAGE_STATS = 1024


@dataclasses.dataclass
class ControllerStats:
    reads: int = 0
    writes: int = 0
    words_read: int = 0
    words_written: int = 0
    detected: int = 0              # words with nonzero syndrome seen on read
    corrected: int = 0             # flagged words the decoder fixed
    uncorrectable: int = 0         # flagged words with residual syndrome
    writebacks: int = 0            # corrected words rewritten into storage
    scrub_rounds: int = 0
    scrub_words: int = 0           # words syndrome-scanned by scrubbing
    scrub_cells: int = 0           # cells scanned (words * n)
    scrub_corrected: int = 0
    scrub_uncorrectable: int = 0
    scrub_seconds: float = 0.0

    CORRECTION_KEYS = ("detected", "corrected", "uncorrectable")

    @property
    def scrub_bandwidth_cells_per_s(self) -> float:
        return self.scrub_cells / self.scrub_seconds if self.scrub_seconds \
            else 0.0

    def as_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["scrub_bandwidth_cells_per_s"] = self.scrub_bandwidth_cells_per_s
        return d

    def merge(self, other: "ControllerStats") -> "ControllerStats":
        """Accumulate another stats block into this one (all counters sum;
        returns self so merges chain)."""
        for f in dataclasses.fields(self):
            setattr(self, f.name,
                    getattr(self, f.name) + getattr(other, f.name))
        return self

    def correction_counts(self) -> dict[str, int]:
        """The read-path correction triple every per-tenant report uses."""
        return {k: getattr(self, k) for k in self.CORRECTION_KEYS}

    @staticmethod
    def add_counts(out: dict[str, int], src) -> dict[str, int]:
        """Add one correction-count source (a `ControllerStats` or any dict
        holding the triple) into `out` in place. The single merge helper
        behind every detected/corrected/uncorrectable summation in the
        serving layer — see `ServingEngine.tenant_stats`."""
        get = src.correction_counts().get if isinstance(
            src, ControllerStats) else src.get
        for k in ControllerStats.CORRECTION_KEYS:
            out[k] = out.get(k, 0) + int(get(k, 0))
        return out

    def publish(self, registry, **labels) -> None:
        """Export every counter into a `MetricsRegistry` as gauges (the
        stats are already cumulative totals, so gauge-set is idempotent
        across repeated publishes — counter-inc would double count)."""
        if registry is None or not getattr(registry, "enabled", False):
            return
        for f in dataclasses.fields(self):
            registry.gauge(f"controller_{f.name}", **labels).set(
                getattr(self, f.name))


class MemoryController:
    """`basic` policy: correct-on-read, storage untouched."""

    policy = "basic"

    def __init__(self, *, n_iters: int = 10, damping: float = 0.3,
                 llv_scale: float = 4.0, llv_mode: str = "manhattan",
                 chunk_size: int = 256, use_sharded: bool | None = None,
                 scan_block: int = 512,
                 page_words: int | None = None, policy=None):
        # `policy=` pins a KernelPolicy for this controller's scans; the
        # class-level `policy` name ("basic"/"writeback"/"scrub") stays the
        # policy *name*, so scrub reports label themselves correctly
        if policy is not None:
            from repro.kernels.backend import _as_policy
            policy = _as_policy(policy)
        self.kernel_policy = policy
        self.n_iters = n_iters
        self.damping = damping
        self.llv_scale = llv_scale
        self.llv_mode = llv_mode
        self.chunk_size = chunk_size
        self.use_sharded = (len(jax.devices()) > 1 if use_sharded is None
                            else use_sharded)
        self.scan_block = scan_block
        self.page_words = page_words          # default paging for sweeps
        self.stats = ControllerStats()
        self._jit_cache: dict[int, tuple[LDPCCode, object]] = {}
        self._scan_cache: dict[int, tuple[LDPCCode, object]] = {}
        self._host_ht_cache: dict[int, tuple[LDPCCode, np.ndarray]] = {}
        self._repair_cache: dict[int, tuple[LDPCCode, RepairQueue]] = {}

    # -- decode plumbing ----------------------------------------------------

    def _decoder(self, code: LDPCCode):
        """One jitted fixed-shape (chunk_size, n) decoder per code."""
        hit = self._jit_cache.get(id(code))
        if hit is not None and hit[0] is code:
            return hit[1]

        if self.use_sharded:
            from repro.distributed.sharding import data_mesh, decode_sharded
            mesh = data_mesh()

            def run(y):
                return decode_sharded(code, y, mesh=mesh,
                                      n_iters=self.n_iters,
                                      llv_scale=self.llv_scale,
                                      llv_mode=self.llv_mode,
                                      damping=self.damping, early_exit=True)
        else:
            def run(y):
                return decode_integers(code, y, n_iters=self.n_iters,
                                       llv_scale=self.llv_scale,
                                       llv_mode=self.llv_mode,
                                       damping=self.damping, early_exit=True)

        fn = jax.jit(run)
        self._jit_cache[id(code)] = (code, fn)
        return fn

    @staticmethod
    def _pad_block(chunk: np.ndarray, size: int, n: int):
        """Zero-pad a ragged tail block to the executable's fixed row count
        (zero words are valid codewords: unflagged, converge immediately).
        Returns (padded int32 block, true row count)."""
        chunk = chunk.astype(np.int32)
        b = chunk.shape[0]
        if b < size:
            chunk = np.concatenate([chunk, np.zeros((size - b, n), np.int32)])
        return chunk, b

    def _repair_queue(self, code: LDPCCode) -> RepairQueue:
        """One coalescing repair queue per code (it owns the bucketed
        decode executables every repair on this controller routes through)."""
        hit = self._repair_cache.get(id(code))
        if hit is not None and hit[0] is code:
            return hit[1]
        q = RepairQueue(code, chunk_size=self.chunk_size,
                        n_iters=self.n_iters, damping=self.damping,
                        llv_scale=self.llv_scale, llv_mode=self.llv_mode,
                        use_sharded=self.use_sharded)
        self._repair_cache[id(code)] = (code, q)
        return q

    def _decode_words(self, code: LDPCCode, words: np.ndarray):
        """Decode (B, n) stored level-words -> (symbols (B, n), fail (B,))
        through the repair queue's bucketed executables (8/16/…/chunk_size
        rows): sparse reads no longer pad to a full chunk, every chunk
        dispatches asynchronously, and one host sync resolves the batch."""
        syms, fail, iters, _pad = self._repair_queue(code).decode_batch(words)
        est = obs_ras.current()
        if est.enabled and iters is not None:
            # outputs are concrete here (post-sync) — feed decoder-stress/
            # fail telemetry to the RAS estimator
            est.observe_decode(iters, self.n_iters, detect_fail=fail)
        return syms, fail

    def _decode_words_legacy(self, code: LDPCCode, words: np.ndarray):
        """The pre-coalescing decode path: every chunk pads to the full
        `chunk_size` executable and syncs to host before the next dispatch.
        Kept as the measured baseline behind `scrub_pages(coalesce=False)`
        (the repair-parity tests and `bench_scrub`'s repair-throughput
        section diff the coalesced pipeline against it)."""
        fn = self._decoder(code)
        est = obs_ras.current()
        B = words.shape[0]
        cs = self.chunk_size
        syms = np.empty((B, code.n), np.int64)
        fail = np.empty(B, bool)
        for lo in range(0, B, cs):
            chunk, b = self._pad_block(words[lo:lo + cs], cs, code.n)
            _y, res = fn(jnp.asarray(chunk))
            syms[lo:lo + b] = np.asarray(res.symbols[:b])  # noqa: RPL007 - per-chunk sync IS the measured baseline
            fail[lo:lo + b] = np.asarray(res.detect_fail[:b])  # noqa: RPL007 - per-chunk sync IS the measured baseline
            if est.enabled:
                iters = getattr(res, "iterations", None)
                if iters is not None:
                    est.observe_decode(np.asarray(iters)[:b], self.n_iters,  # noqa: RPL007 - concrete post-sync values
                                       detect_fail=fail[lo:lo + b])
        return syms, fail

    # -- syndrome-scan backends ---------------------------------------------

    def _scan_mode(self) -> str:
        """Resolved kernel mode for scans: the controller's pinned policy,
        else the ambient one."""
        from repro.kernels.backend import current_policy
        return (self.kernel_policy or current_policy()).resolve()

    def resolved_scan_backend(self) -> str:
        # "ref" mode is the host BLAS/int64 scan; compiled and interpret
        # both run the device (Pallas) scan executable. Matches the legacy
        # scan_backend mapping: auto -> device only on TPU, host -> ref,
        # device -> Pallas (interpreted off-TPU).
        return "host" if self._scan_mode() == "ref" else "device"

    def _scan_route(self, code: LDPCCode) -> str:
        """The backend a scan of `code` ACTUALLY runs on: the device kernel
        takes int8 operands and accumulates in int32, so fields past int8
        and fields/words beyond its exact bound route to the host scan
        (whose own fallback is int64) even when the device backend is
        configured."""
        from repro.kernels.ops import MAX_KERNEL_FIELD
        if (self.resolved_scan_backend() == "device"
                and code.n * (code.p - 1) ** 2 < 2 ** 31
                and code.p <= MAX_KERNEL_FIELD):
            return "device"
        return "host"

    def _scan_syndromes(self, code: LDPCCode, enc: np.ndarray) -> np.ndarray:
        """Syndrome scan -> flagged mask (B,). This is the cheap always-on
        part of the read path; decode runs only on flags."""
        if self._scan_route(code) == "device":
            return self._scan_syndromes_device(code, enc)
        return self._scan_syndromes_host(code, enc)

    def _host_ht(self, code: LDPCCode, dtype) -> np.ndarray:
        """Per-code cache of the transposed+cast check matrix: paged sweeps
        call the host scan once per page, and the (c, n) conversion must not
        be repaid on every page (mirrors `_scanner`'s cached executable)."""
        hit = self._host_ht_cache.get(id(code))
        if hit is not None and hit[0] is code and hit[1].dtype == dtype:
            return hit[1]
        ht = code.H.T.astype(dtype)
        self._host_ht_cache[id(code)] = (code, ht)
        return ht

    def _scan_syndromes_host(self, code: LDPCCode,
                             enc: np.ndarray) -> np.ndarray:
        """float32 BLAS scan (NumPy integer matmul is a slow C loop — this
        is the host scrub-bandwidth hot path), exact while every accumulated
        product is bounded by n*(p-1)^2 < 2^24; large-field / long-word
        codes beyond that fall back to the exact int64 path."""
        if code.n * (code.p - 1) ** 2 < 2 ** 24:
            s = (enc.astype(np.float32)
                 @ self._host_ht(code, np.float32)).astype(np.int64)
        else:
            s = enc.astype(np.int64) @ self._host_ht(code, np.int64)
        return np.any(s % code.p != 0, axis=1)

    def _scanner(self, code: LDPCCode):
        """One jitted fixed-shape (scan_block, n) fused scan per code,
        shard_map'd over the local device mesh when more than one device is
        visible (same dispatch shape as `_decoder`)."""
        hit = self._scan_cache.get(id(code))
        if hit is not None and hit[0] is code:
            return hit[1]

        if self.use_sharded:
            from repro.distributed.sharding import (data_mesh,
                                                    scan_syndromes_sharded)
            mesh = data_mesh()

            def run(y):
                return scan_syndromes_sharded(code, y, mesh=mesh)
        else:
            from repro.kernels.ops import scan_syndromes
            ht = jnp.asarray(code.H.T, jnp.int32)
            # bake the resolved interpret flag in at build time so a later
            # ambient-policy change can't retarget this cached executable
            interp = self._scan_mode() != "compiled"

            def run(y):
                return scan_syndromes(y, ht, code.p, interpret=interp)

        fn = jax.jit(run)
        self._scan_cache[id(code)] = (code, fn)
        return fn

    def _scan_syndromes_device(self, code: LDPCCode,
                               enc: np.ndarray) -> np.ndarray:
        """Fused Pallas scan: pages are streamed through one cached
        executable in fixed `scan_block`-row slices (zero-padded tails are
        valid codewords — never flagged); only the (b,) mask comes back.
        Every block scan is dispatched before any mask is pulled, so the
        device pipelines the whole page and the host syncs exactly once."""
        fn = self._scanner(code)
        B = enc.shape[0]
        sb = self.scan_block
        launched = []
        for lo in range(0, B, sb):
            blk, b = self._pad_block(enc[lo:lo + sb], sb, code.n)
            launched.append((fn(jnp.asarray(blk)), b))
        masks = jax.device_get([m for m, _ in launched])
        flags = np.empty(B, bool)
        lo = 0
        for mask, (_dev, b) in zip(masks, launched, strict=True):
            flags[lo:lo + b] = mask[:b]
            lo += b
        return flags

    def _correct(self, code: LDPCCode, enc: np.ndarray):
        """-> (corrected levels (B, n), flagged, fail) without stats."""
        flagged = self._scan_syndromes(code, enc)
        out = enc.astype(np.int64) % code.p
        fail = np.zeros(enc.shape[0], bool)
        if flagged.any():
            syms, f = self._decode_words(code, enc[flagged])
            out[flagged] = syms
            fail[flagged] = f
        return out, flagged, fail

    # -- policy surface -----------------------------------------------------

    def read(self, code: LDPCCode, store: dict, name: str) -> np.ndarray:
        st = store[name]
        out, flagged, fail = self._correct(code, st.enc)
        n_flagged = int(flagged.sum())
        n_fail = int(fail.sum())
        self.stats.reads += 1
        self.stats.words_read += st.enc.shape[0]
        self.stats.detected += n_flagged
        self.stats.corrected += n_flagged - n_fail
        self.stats.uncorrectable += n_fail
        reg = obs_metrics.current()
        if reg.enabled:
            labels = {"layer": "controller", "policy": self.policy,
                      "code": f"gf{code.p}n{code.n}"}
            reg.counter("mem_words_read", **labels).inc(st.enc.shape[0])
            reg.counter("mem_detected", **labels).inc(n_flagged)
            reg.counter("mem_corrected", **labels).inc(n_flagged - n_fail)
            reg.counter("mem_uncorrectable", **labels).inc(n_fail)
        est = obs_ras.current()
        if est.enabled:
            est.observe_scan(n_flagged, st.enc.shape[0], n_symbols=code.n)
        self._writeback(st, out, flagged, fail)
        return out

    def _writeback(self, st, corrected: np.ndarray, flagged: np.ndarray,
                   fail: np.ndarray) -> None:
        pass                        # basic: never touch storage

    def note_write(self, n_words: int) -> None:
        self.stats.writes += 1
        self.stats.words_written += n_words

    def tick(self, code: LDPCCode, store: dict) -> None:
        pass                        # only the scrub policy acts on ticks

    @staticmethod
    def iter_pages(store: dict,
                   page_words: int | None = None) -> Iterator[np.ndarray]:
        """Yield writable (b, n) row views over the stored words —
        `page_words` rows per page (ragged tails allowed), or one page per
        tensor when None. Repairs written into a page propagate to backing
        storage, so any page iterator with the same contract (e.g. over an
        mmap'd checkpoint archive) can be fed to `scrub_pages` directly."""
        if page_words is not None and page_words <= 0:
            raise ValueError(f"page_words must be positive, got {page_words}")

        def gen():
            for st in store.values():
                enc = st.enc
                if page_words is None:
                    yield enc
                else:
                    for lo in range(0, enc.shape[0], page_words):
                        yield enc[lo:lo + page_words]
        return gen()

    def scrub(self, code: LDPCCode, store: dict, *,
              page_words: int | None = None, coalesce: bool = True) -> dict:
        """Full-array sweep: scan every stored word, repair flagged words in
        place (every policy may be scrubbed explicitly; only
        `ScrubController` does it automatically). `page_words` (default: the
        controller's `page_words`) streams the sweep in fixed-size pages so
        arrays larger than device memory scrub incrementally. Returns a
        report with the sweep's counts, scan bandwidth, and per-page stats."""
        if page_words is None:
            page_words = self.page_words
        return self.scrub_pages(code, self.iter_pages(store, page_words),
                                page_words=page_words, coalesce=coalesce)

    def scrub_pages(self, code: LDPCCode, pages: Iterable[np.ndarray], *,
                    page_words: int | None = None, coalesce: bool = True,
                    scan_ahead: int = 4,
                    drain_words: int | None = None) -> dict:
        """Paged sweep over any iterator of writable (b, n) level-word
        pages: scan each page (host BLAS or the fused device kernel, per
        the resolved kernel policy), batch-decode only the flagged words,
        and write repairs back through the page views. Pages are consumed
        lazily, so arrays larger than device memory stream through.

        `coalesce=True` (default) runs the repair pipeline: pages are
        scanned `scan_ahead` ahead while earlier pages' flagged rows sit on
        the cross-page `RepairQueue`, which drains through bucketed decode
        executables once `drain_words` rows accumulate (one host sync per
        scan window and one per drain, instead of one per page and per
        chunk). `coalesce=False` keeps the per-page scan→pad→decode→sync
        baseline the pipeline is benchmarked against. Both produce
        bit-identical repairs (FBP is row-independent)."""
        if coalesce:
            return self._scrub_pages_coalesced(
                code, pages, page_words=page_words, scan_ahead=scan_ahead,
                drain_words=drain_words)
        return self._scrub_pages_baseline(code, pages, page_words=page_words)

    def _scrub_pages_baseline(self, code: LDPCCode,
                              pages: Iterable[np.ndarray], *,
                              page_words: int | None = None) -> dict:
        """Per-page sweep: one scan sync and one full-`chunk_size` decode
        dispatch train per flagged page (the pre-pipeline behavior)."""
        t0 = time.perf_counter()
        words = flagged_n = corrected_n = fail_n = n_pages = 0
        page_stats = []
        est = obs_ras.current()
        reg = obs_metrics.current()
        for page in pages:
            n_pages += 1
            tp = time.perf_counter()
            # scan-only on clean pages: the full corrected-levels copy that
            # `_correct` builds for reads is skipped, decode touches only
            # flagged rows, and repairs come straight from decoder symbols
            flagged = self._scan_syndromes(code, page)
            pg_flagged = int(flagged.sum())
            pg_fail = 0
            if pg_flagged:
                syms, f = self._decode_words_legacy(code, page[flagged])
                pg_fail = int(f.sum())
                rows = np.flatnonzero(flagged)[~f]
                if rows.size:
                    page[rows] = syms[~f].astype(page.dtype)
            words += page.shape[0]
            flagged_n += pg_flagged
            corrected_n += pg_flagged - pg_fail
            fail_n += pg_fail
            if est.enabled:
                est.observe_scan(pg_flagged, page.shape[0],
                                 n_symbols=code.n)
            if reg.enabled:
                reg.histogram("scrub_page_seconds",
                              layer="controller").observe(
                    time.perf_counter() - tp)
            if n_pages <= MAX_PAGE_STATS:
                page_stats.append({
                    "words": int(page.shape[0]), "flagged": pg_flagged,
                    "corrected": pg_flagged - pg_fail,
                    "uncorrectable": pg_fail,
                    "seconds": time.perf_counter() - tp})
        dt = time.perf_counter() - t0
        self._note_scrub_totals(code, words, corrected_n, fail_n, dt)
        return {"policy": self.policy, "backend": self._scan_route(code),
                "words_scanned": words,
                "cells_scanned": words * code.n, "flagged": flagged_n,
                "corrected": corrected_n, "uncorrectable": fail_n,
                "pages": n_pages, "page_words": page_words,
                "page_stats": page_stats,
                "page_stats_truncated": n_pages > MAX_PAGE_STATS,
                "coalesced": False, "seconds": dt,
                "bandwidth_cells_per_s": words * code.n / dt if dt else 0.0}

    def _scrub_pages_coalesced(self, code: LDPCCode,
                               pages: Iterable[np.ndarray], *,
                               page_words: int | None = None,
                               scan_ahead: int = 4,
                               drain_words: int | None = None) -> dict:
        """The repair pipeline: double-buffered page windows keep scans in
        flight while the previous window's masks resolve in one transfer;
        flagged rows coalesce on the `RepairQueue` across pages and drain
        through bucketed decode executables (again one sync per drain)."""
        t0 = time.perf_counter()
        scan_ahead = max(1, scan_ahead)
        if drain_words is None:
            drain_words = 4 * self.chunk_size
        queue = self._repair_queue(code)
        route = self._scan_route(code)
        fn = self._scanner(code) if route == "device" else None
        est = obs_ras.current()
        reg = obs_metrics.current()
        totals = {"words": 0, "flagged": 0, "corrected": 0,
                  "uncorrectable": 0, "pages": 0}
        page_stats: list[dict] = []
        drain_stats: list[dict] = []

        def flush():
            rep = queue.drain()
            if rep["words"]:
                totals["corrected"] += rep["repaired"]
                totals["uncorrectable"] += rep["failed"]
                drain_stats.append({k: rep[k] for k in (
                    "entries", "words", "repaired", "failed", "pad_rows",
                    "dispatch_rows", "pad_waste", "seconds")})

        def scan_dispatch(page):
            """Dispatch one page's scan without syncing: the device route
            returns in-flight (mask, rows) pairs per scan block; the host
            route computes the np mask eagerly (it never leaves the host)."""
            if fn is None:
                return self._scan_syndromes_host(code, page)
            out = []
            sb = self.scan_block
            for lo in range(0, page.shape[0], sb):
                blk, b = self._pad_block(page[lo:lo + sb], sb, code.n)
                out.append((fn(jnp.asarray(blk)), b))
            return out

        def consume(window):
            """Resolve one scanned window — a single host sync pulls every
            block mask while the next window's scans and any queued decodes
            stay in flight — then enqueue the flagged rows."""
            if not window:
                return
            if fn is not None:
                flat = iter(jax.device_get(
                    [m for _pg, blocks in window for m, _b in blocks]))
            for page, scanned in window:
                if fn is not None:
                    mask = np.empty(page.shape[0], bool)
                    lo = 0
                    for _dev, b in scanned:
                        mask[lo:lo + b] = next(flat)[:b]
                        lo += b
                else:
                    mask = scanned
                totals["pages"] += 1
                totals["words"] += page.shape[0]
                rows = np.flatnonzero(mask)
                pg_flagged = int(rows.size)
                totals["flagged"] += pg_flagged
                if est.enabled:
                    est.observe_scan(pg_flagged, page.shape[0],
                                     n_symbols=code.n)
                slot = None
                if totals["pages"] <= MAX_PAGE_STATS:
                    slot = {"words": int(page.shape[0]),
                            "flagged": pg_flagged, "corrected": 0,
                            "uncorrectable": 0}
                    page_stats.append(slot)
                if not pg_flagged:
                    continue

                def writeback(syms, ok, page=page, rows=rows, slot=slot):
                    good = rows[ok]
                    if good.size:
                        page[good] = syms[ok].astype(page.dtype)
                    if slot is not None:
                        slot["corrected"] = int(ok.sum())
                        slot["uncorrectable"] = int((~ok).sum())

                queue.enqueue(page[rows], writeback,
                              provenance=("page", totals["pages"] - 1, rows))

        prev: list = []
        cur: list = []
        for page in pages:
            cur.append((page, scan_dispatch(page)))
            if len(cur) >= scan_ahead:
                consume(prev)
                prev, cur = cur, []
                if queue.pending_words >= drain_words:
                    flush()
        consume(prev)
        consume(cur)
        flush()
        dt = time.perf_counter() - t0
        words, corrected_n, fail_n = (totals["words"], totals["corrected"],
                                      totals["uncorrectable"])
        self._note_scrub_totals(code, words, corrected_n, fail_n, dt)
        if reg.enabled and drain_stats:
            reg.histogram("scrub_drains_per_sweep",
                          layer="controller").observe(len(drain_stats))
        pad_rows = sum(d["pad_rows"] for d in drain_stats)
        dispatch_rows = sum(d["dispatch_rows"] for d in drain_stats)
        return {"policy": self.policy, "backend": route,
                "words_scanned": words,
                "cells_scanned": words * code.n,
                "flagged": totals["flagged"],
                "corrected": corrected_n, "uncorrectable": fail_n,
                "pages": totals["pages"], "page_words": page_words,
                "page_stats": page_stats,
                "page_stats_truncated": totals["pages"] > MAX_PAGE_STATS,
                "coalesced": True, "scan_ahead": scan_ahead,
                "drains": len(drain_stats), "drain_stats": drain_stats,
                "repair_pad_rows": pad_rows,
                "repair_dispatch_rows": dispatch_rows,
                "repair_pad_waste": (pad_rows / dispatch_rows
                                     if dispatch_rows else 0.0),
                "seconds": dt,
                "bandwidth_cells_per_s": words * code.n / dt if dt else 0.0}

    def _note_scrub_totals(self, code: LDPCCode, words: int, corrected_n: int,
                           fail_n: int, dt: float) -> None:
        """Shared sweep accounting: cumulative `ControllerStats` counters
        plus the metrics-registry export (both sweep flavors report the
        same way)."""
        self.stats.scrub_rounds += 1
        self.stats.scrub_words += words
        self.stats.scrub_cells += words * code.n
        self.stats.scrub_corrected += corrected_n
        self.stats.scrub_uncorrectable += fail_n
        self.stats.scrub_seconds += dt
        reg = obs_metrics.current()
        if reg.enabled:
            labels = {"layer": "controller", "policy": self.policy,
                      "code": f"gf{code.p}n{code.n}"}
            reg.counter("scrub_words_scanned", **labels).inc(words)
            reg.counter("scrub_corrected", **labels).inc(corrected_n)
            reg.counter("scrub_uncorrectable", **labels).inc(fail_n)


class WritebackController(MemoryController):
    """`writeback` policy: reads repair storage as a side effect."""

    policy = "writeback"

    def _writeback(self, st, corrected, flagged, fail):
        ok = flagged & ~fail
        if ok.any():
            st.enc[ok] = corrected[ok].astype(st.enc.dtype)
            self.stats.writebacks += int(ok.sum())


class ScrubController(WritebackController):
    """`scrub` policy: writeback + a background sweep every `interval`
    read/write operations."""

    policy = "scrub"

    def __init__(self, *, interval: int = 16, **kw):
        super().__init__(**kw)
        self.interval = interval
        self._ops = 0

    def tick(self, code: LDPCCode, store: dict) -> None:
        self._ops += 1
        if self._ops % self.interval == 0:
            self.scrub(code, store)


_POLICIES = {"basic": MemoryController, "writeback": WritebackController,
             "scrub": ScrubController}


def make_controller(spec, **kw) -> MemoryController:
    """spec: a policy name ("basic" | "writeback" | "scrub"), a controller
    instance (passed through), or None (basic)."""
    if isinstance(spec, MemoryController):
        return spec
    if spec is None:
        spec = "basic"
    if spec not in _POLICIES:
        raise KeyError(f"unknown controller policy {spec!r}; "
                       f"available: {sorted(_POLICIES)}")
    return _POLICIES[spec](**kw)
