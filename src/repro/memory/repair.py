"""Coalescing repair pipeline: cross-page flagged-word batching.

The scan -> gated-decode split (the paper's efficiency argument) only pays
off if sparse flags stay cheap to *repair*: at raw BER 1e-3 a page of 256
words carries a handful of flagged rows, and padding each page's flags to a
full `chunk_size` FBP dispatch — then syncing before the next page — makes
decode dispatch, not the scan, the sweep bottleneck (the dataflow
interruption the high-throughput memristive-ECC line warns about).

`RepairQueue` decouples flag discovery from repair:

- **accumulate** — `enqueue()` collects flagged (b, n) level-word batches
  from anywhere (controller pages, paged-store pages, every tenant of a
  shared pool), each with a writeback closure, an owner label for
  per-tenant attribution, and (store, page, rows) provenance;
- **bucketed decode** — `drain()` concatenates everything queued and runs
  it through power-of-two-bucketed decode executables (8/16/.../chunk_size
  rows, the `np_bucket` idiom from `attend_protected`), so 3 flagged words
  pay a ~8-row FBP instead of a `chunk_size`-row one, while dense batches
  still use the full-width executable. Executables are cached process-wide
  per (code, decode params, rows), and a drain prefers padding up to an
  already-warm bucket over compiling its exact size — FBP compiles cost
  seconds on CPU, pad rows cost microseconds;
- **one sync per drain** — every bucket decode is dispatched
  asynchronously, then a single `jax.device_get` resolves the whole train;
  repairs scatter back through the writebacks afterward. FBP is row-
  independent (per-codeword early exit), so decoding rows in a coalesced
  batch is bit-exact with decoding them per page.

On accelerator backends the bucket executables donate their input buffer
(the padded flagged-row batch is dead after dispatch); CPU jit ignores
donation, so it is gated off there to avoid the warning.

Queue depth, pad-waste ratio, and drain latency feed `repro.obs` metrics;
decode iteration vectors feed the RAS estimator per owner region — all
no-ops unless the ambient telemetry is installed.
"""
from __future__ import annotations

import dataclasses
import time
from collections.abc import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.construction import LDPCCode
from repro.core.decode import decode_integers
from repro.kernels.ops import np_bucket
from repro.obs import metrics as obs_metrics
from repro.obs import ras as obs_ras
from repro.obs.trace import span

__all__ = ["RepairQueue", "bucket_sizes"]


def bucket_sizes(chunk_size: int, min_bucket: int = 8) -> list[int]:
    """The decode-executable row counts a queue of `chunk_size` may build:
    powers of two from `min_bucket` up, capped by (and always including)
    `chunk_size` itself."""
    sizes = []
    b = min(min_bucket, chunk_size)
    while b < chunk_size:
        sizes.append(b)
        b *= 2
    sizes.append(chunk_size)
    return sizes


# process-wide decode-executable cache, keyed by (decode config, bucket
# rows): every queue on the same code/params shares warm executables, so a
# bench's warm run (or a sibling tenant's sweep) pays the compile, not the
# timed region. Executables close over their code object, so the id() key
# can never be reused while its entry lives.
_DECODER_CACHE: dict[tuple, dict[int, object]] = {}


@dataclasses.dataclass
class _Entry:
    """One enqueued batch of flagged rows awaiting the next drain."""

    words: object               # (rows, n) flagged level-words (np or jnp)
    writeback: Callable         # (symbols (rows, n) int64, ok (rows,))
                                # -> bytes written back to the device, or None
    owner: object               # tenant label for per-owner attribution
    provenance: tuple           # e.g. ("pool", page_id, row_indices)
    rows: int


class RepairQueue:
    """Accumulates flagged codeword rows across pages/stores/tenants and
    drains them through bucketed decode executables with one host sync."""

    def __init__(self, code: LDPCCode, *, chunk_size: int = 256,
                 min_bucket: int = 8, n_iters: int = 10,
                 damping: float = 0.3, llv_scale: float = 4.0,
                 llv_mode: str = "manhattan", use_sharded: bool = False,
                 donate: bool | None = None):
        self.code = code
        self.chunk_size = int(chunk_size)
        self.min_bucket = min(int(min_bucket), self.chunk_size)
        self.n_iters = n_iters
        self.damping = damping
        self.llv_scale = llv_scale
        self.llv_mode = llv_mode
        self.use_sharded = use_sharded
        # donating the padded input buffer lets XLA reuse it for the decode
        # workspace on TPU/GPU; CPU jit warns-and-ignores, so gate it off
        self.donate = (jax.default_backend() != "cpu" if donate is None
                       else donate)
        self._decoders = _DECODER_CACHE.setdefault(
            (id(code), n_iters, damping, llv_scale, llv_mode, use_sharded,
             self.donate), {})
        self._entries: list[_Entry] = []
        self._pending = 0
        # lifetime totals (exposed so benches/tests can read pad waste
        # without the metrics registry installed)
        self.drains = 0
        self.total_rows = 0
        self.total_pad_rows = 0
        self.total_repaired = 0
        self.total_failed = 0

    # -- bucketed executables -----------------------------------------------

    def bucket_for(self, rows: int) -> int:
        """Smallest decode bucket that fits `rows` (power of two, floor
        `min_bucket`, cap `chunk_size`)."""
        return min(self.chunk_size, max(self.min_bucket, np_bucket(rows)))

    def _dispatch_size(self, rows: int) -> int:
        """Bucket to actually dispatch `rows` on: the ideal `bucket_for`
        size if it is already compiled (or nothing bigger is), else the
        smallest compiled bucket that fits. Padding a drain up to a warm
        executable costs microseconds of extra FBP rows; compiling a new
        bucket costs ~seconds on CPU — never pay a compile a warm bucket
        could absorb."""
        want = self.bucket_for(rows)
        if want in self._decoders:
            return want
        compiled = [s for s in self._decoders
                    if want < s <= self.chunk_size]
        return min(compiled) if compiled else want

    def _decoder(self, size: int):
        """One cached fixed-shape (size, n) decode executable per bucket."""
        fn = self._decoders.get(size)
        if fn is not None:
            return fn
        code = self.code
        kw = dict(n_iters=self.n_iters, damping=self.damping,
                  llv_scale=self.llv_scale, llv_mode=self.llv_mode,
                  early_exit=True)
        run = None
        if self.use_sharded:
            from repro.core.protected import np_prod_mesh
            from repro.distributed.sharding import data_mesh, decode_sharded
            mesh = data_mesh()
            if size % np_prod_mesh(mesh) == 0:
                def run(y):
                    return decode_sharded(code, y, mesh=mesh, **kw)
        if run is None:
            def run(y):
                return decode_integers(code, y, **kw)
        donate = self.donate and not self.use_sharded
        fn = jax.jit(run, donate_argnums=(0,)) if donate else jax.jit(run)
        self._decoders[size] = fn
        return fn

    def _pad(self, words, size: int):
        """Zero-pad (b, n) rows up to the bucket's fixed row count (zero
        words are valid codewords: unflagged, converge immediately). Works
        on host or device arrays without forcing a transfer."""
        xp = np if isinstance(words, np.ndarray) else jnp
        words = words.astype(xp.int32)
        b = words.shape[0]
        if b < size:
            words = xp.concatenate(
                [words, xp.zeros((size - b, self.code.n), xp.int32)])
        return words

    def decode_batch(self, words):
        """Decode (B, n) flagged level-words through the bucketed
        executables: full `chunk_size` chunks plus a bucketed tail, every
        dispatch asynchronous, then ONE host sync for the whole train.
        Returns (symbols (B, n) int64, fail (B,), iterations (B,) | None,
        pad_rows)."""
        B = int(words.shape[0])
        if B == 0:
            return (np.zeros((0, self.code.n), np.int64),
                    np.zeros(0, bool), None, 0)
        cs = self.chunk_size
        launched = []
        pad_rows = nbytes = 0
        with span("repair.decode") as sp:
            for lo in range(0, B, cs):
                chunk = words[lo:lo + cs]
                b = int(chunk.shape[0])
                size = self._dispatch_size(b)
                pad_rows += size - b
                padded = self._pad(chunk, size)
                nbytes += padded.nbytes
                _y, res = self._decoder(size)(jnp.asarray(padded))
                launched.append((res, b))
            # the drain's single sync: every bucket decode is in flight
            pulled = jax.device_get(
                [(r.symbols, r.detect_fail, getattr(r, "iterations", None))
                 for r, _ in launched])
            nbytes += sum(a.nbytes for t in pulled for a in t
                          if a is not None)
            sp.set(dispatches=len(launched), bytes=nbytes)
        syms = np.empty((B, self.code.n), np.int64)
        fail = np.empty(B, bool)
        have_iters = all(t[2] is not None for t in pulled)
        iters = np.empty(B, np.int64) if have_iters else None
        lo = 0
        for (s, f, it), (_res, b) in zip(pulled, launched, strict=True):
            syms[lo:lo + b] = s[:b]
            fail[lo:lo + b] = f[:b]
            if have_iters:
                iters[lo:lo + b] = it[:b]
            lo += b
        self.total_rows += B
        self.total_pad_rows += pad_rows
        return syms, fail, iters, pad_rows

    # -- queue surface ------------------------------------------------------

    def __len__(self) -> int:
        return len(self._entries)

    @property
    def pending_words(self) -> int:
        return self._pending

    def enqueue(self, words, writeback, *, owner=None,
                provenance: tuple = ()) -> None:
        """Queue (rows, n) flagged level-words for the next drain.
        `writeback(symbols, ok)` is called with the decoded (rows, n) int64
        symbols and the (rows,) repaired mask, and may return the bytes it
        wrote back to the device (counted on the `repair.writeback` span);
        `owner` labels the rows for per-tenant attribution in the drain
        report."""
        rows = int(words.shape[0])
        if rows == 0:
            return
        self._entries.append(
            _Entry(words, writeback, owner, tuple(provenance), rows))
        self._pending += rows

    def drain(self) -> dict:
        """Decode everything queued as one coalesced bucketed dispatch
        train (single host sync), scatter repairs through each entry's
        writeback, and report words / repaired / pad waste / by_owner."""
        entries, self._entries = self._entries, []
        pending, self._pending = self._pending, 0
        if not entries:
            return {"entries": 0, "words": 0, "repaired": 0, "failed": 0,
                    "pad_rows": 0, "dispatch_rows": 0, "pad_waste": 0.0,
                    "by_owner": {}, "seconds": 0.0}
        t0 = time.perf_counter()
        if len(entries) == 1:
            batch = entries[0].words
        elif all(isinstance(e.words, np.ndarray) for e in entries):
            batch = np.concatenate([e.words for e in entries])
        else:
            batch = jnp.concatenate(
                [jnp.asarray(e.words, jnp.int32) for e in entries])
        syms, fail, iters, pad_rows = self.decode_batch(batch)
        est = obs_ras.current()
        by_owner: dict[object, dict] = {}
        with span("repair.writeback") as sp:
            lo = nbytes = 0
            for e in entries:
                s = syms[lo:lo + e.rows]
                f = fail[lo:lo + e.rows]
                ok = ~f
                nbytes += e.writeback(s, ok) or 0
                ent = by_owner.setdefault(
                    e.owner, {"flagged_words": 0, "repaired_words": 0})
                ent["flagged_words"] += e.rows
                ent["repaired_words"] += int(ok.sum())
                if est.enabled and iters is not None:
                    est.observe_decode(iters[lo:lo + e.rows], self.n_iters,
                                       detect_fail=f,
                                       region=str(e.owner)
                                       if e.owner is not None else "")
                lo += e.rows
            sp.set(bytes=nbytes)
        dt = time.perf_counter() - t0
        repaired = int((~fail).sum())
        failed = pending - repaired
        self.drains += 1
        self.total_repaired += repaired
        self.total_failed += failed
        reg = obs_metrics.current()
        if reg.enabled:
            reg.histogram("repair_queue_depth", layer="repair").observe(
                pending)
            reg.histogram("repair_drain_seconds", layer="repair").observe(dt)
            reg.counter("repair_drains", layer="repair").inc()
            reg.counter("repair_rows", layer="repair").inc(pending)
            reg.counter("repair_pad_rows", layer="repair").inc(pad_rows)
            reg.counter("repair_repaired", layer="repair").inc(repaired)
            reg.counter("repair_uncorrectable", layer="repair").inc(failed)
        dispatch_rows = pending + pad_rows
        return {"entries": len(entries), "words": pending,
                "repaired": repaired, "failed": failed,
                "pad_rows": pad_rows, "dispatch_rows": dispatch_rows,
                "pad_waste": pad_rows / dispatch_rows if dispatch_rows
                else 0.0,
                "by_owner": by_owner, "seconds": dt}

    @property
    def pad_waste(self) -> float:
        """Lifetime fraction of dispatched decode rows that were padding."""
        total = self.total_rows + self.total_pad_rows
        return self.total_pad_rows / total if total else 0.0
