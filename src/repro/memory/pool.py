"""Shared protected page pool + block allocator for multi-tenant serving.

The single-tenant `PagedProtectedStore` owns grow-only pages, which is right
for one sequence but wasteful across many: every tenant compiles nothing new
(the executables are shape-keyed on `(page_words, n)`), yet each holds
private device buffers it may barely fill, and nothing can reclaim a retired
tenant's pages. This module supplies the vLLM-style layer underneath:

- **`ProtectedPagePool`** — a fixed capacity of `(page_words, n)` GF-level
  pages with a free list, reference counts (so prefix-shared sequences can
  alias blocks), per-page owner labels and last-touch stamps (LRU / cold
  selection), and an incremental `scrub()` that sweeps cold pages with the
  same fused scan -> gated decode -> writeback path the stores use,
  attributing repairs to the owning tenant. The sweep order is round-robin
  by default, or flag-EWMA-prioritized (`prioritize=True`) so a small page
  budget lands on hot-flagging pages — the estimator-driven schedule
  `repro.serving.ServingEngine` drives via
  `repro.obs.ErrorRateEstimator.adaptive_interval`.
- **`PooledStore`** — a `PagedProtectedStore` subclass whose storage
  primitives address the pool through a per-tenant **block table** instead
  of a private list. Writes to a shared page copy-on-write; `free()` returns
  the pages to the pool; `fork()` clones a store by aliasing its blocks
  (prefix sharing). Encode/scan/decode executables are delegated to the
  pool's template store, so every tenant shares one cached jit per shape.

Allocation failure raises `PoolExhausted` *before* any state is mutated —
the serving engine preflights capacity and evicts, and a caller that races
anyway gets a clean error, never a corrupted block table.
"""
from __future__ import annotations

from collections.abc import Iterator

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.construction import LDPCCode
from repro.obs import metrics as obs_metrics
from repro.obs import ras as obs_ras

from .channel import apply_faults
from .controller import ControllerStats
from .paged import PagedProtectedStore

__all__ = ["PoolExhausted", "ProtectedPagePool", "PooledStore"]


class PoolExhausted(RuntimeError):
    """Raised when an allocation needs more pages than the pool has free.

    Raised before any block table or pool state is mutated, so callers can
    evict and retry."""


class ProtectedPagePool:
    """Fixed-capacity pool of (page_words, n) GF pages with a free list,
    ref counts, owner labels, and incremental cold-page scrubbing."""

    def __init__(self, code: str | LDPCCode = "wl1024_r08", *,
                 page_words: int = 256, capacity_pages: int = 64,
                 mesh=None, n_iters: int = 10, damping: float = 0.3,
                 llv_scale: float = 4.0, llv_mode: str = "manhattan",
                 policy=None):
        if capacity_pages <= 0:
            raise ValueError(
                f"capacity_pages must be positive, got {capacity_pages}")
        # the template store carries the code, validation, and the cached
        # encode/scan/decode executables every PooledStore delegates to
        self._template = PagedProtectedStore(
            code, page_words=page_words, mesh=mesh, n_iters=n_iters,
            damping=damping, llv_scale=llv_scale, llv_mode=llv_mode,
            policy=policy)
        self.code = self._template.code
        self.page_words = page_words
        self.mesh = mesh
        self.policy = self._template.policy
        self.capacity_pages = capacity_pages
        self._storage: list[jnp.ndarray | None] = [None] * capacity_pages
        self._refcount = [0] * capacity_pages
        self._owner: list[object | None] = [None] * capacity_pages
        self._stamp = [0] * capacity_pages     # last touch (engine step)
        self._free = list(range(capacity_pages - 1, -1, -1))  # pop() -> 0,1,…
        self._scrub_cursor = 0
        # per-page scrub-flag EWMA + scanned marker: the signal behind
        # prioritized sweeps (hot-flagging pages first) and the RAS
        # estimator's per-owner region feed
        self._flag_ewma = [0.0] * capacity_pages
        self._scanned = [False] * capacity_pages
        self.flag_alpha = 0.3
        self.stats = ControllerStats()         # pool-level scrub aggregates
        self.scrub_by_owner: dict[object, dict] = {}

    # -- introspection ------------------------------------------------------

    @property
    def available(self) -> int:
        return len(self._free)

    @property
    def n_allocated(self) -> int:
        return self.capacity_pages - len(self._free)

    def refcount(self, pid: int) -> int:
        return self._refcount[pid]

    def owner(self, pid: int):
        return self._owner[pid]

    # -- allocator ----------------------------------------------------------

    def alloc(self, owner=None) -> int:
        """Take one zeroed page off the free list. Raises `PoolExhausted`
        (mutating nothing) when the pool is full."""
        if not self._free:
            raise PoolExhausted(
                f"pool exhausted: all {self.capacity_pages} pages allocated")
        pid = self._free.pop()
        self._storage[pid] = self._template._new_page()
        self._refcount[pid] = 1
        self._owner[pid] = owner
        self._stamp[pid] = 0
        self._flag_ewma[pid] = 0.0
        self._scanned[pid] = False
        return pid

    def ref(self, pid: int) -> None:
        """Add an aliasing reference (prefix-shared block tables)."""
        if self._refcount[pid] <= 0:
            raise ValueError(f"page {pid} is not allocated")
        self._refcount[pid] += 1

    def free(self, pid: int) -> None:
        """Drop one reference; the page returns to the free list when the
        last reference goes."""
        if self._refcount[pid] <= 0:
            raise ValueError(f"page {pid} is not allocated")
        self._refcount[pid] -= 1
        if self._refcount[pid] == 0:
            self._storage[pid] = None
            self._owner[pid] = None
            self._flag_ewma[pid] = 0.0
            self._scanned[pid] = False
            self._free.append(pid)

    # -- page access --------------------------------------------------------

    def page(self, pid: int) -> jnp.ndarray:
        pg = self._storage[pid]
        if pg is None:
            raise ValueError(f"page {pid} is not allocated")
        return pg

    def set_page(self, pid: int, page: jnp.ndarray) -> None:
        if self._storage[pid] is None:
            raise ValueError(f"page {pid} is not allocated")
        self._storage[pid] = page

    def touch(self, pid: int, step: int) -> None:
        """Record that `pid` was accessed at engine step `step` (drives the
        cold-page selection below and the engine's LRU eviction)."""
        self._stamp[pid] = step

    def stamp(self, pid: int) -> int:
        return self._stamp[pid]

    # -- background scrub ---------------------------------------------------

    def page_flag_rate(self, pid: int) -> float:
        """EWMA fraction of this page's words flagged across scrub scans
        (0.0 until the first scan)."""
        return self._flag_ewma[pid]

    def hot_pages(self, top: int | None = None) -> list[int]:
        """Allocated pages ranked for scrubbing: never-scanned pages first
        (coverage), then by descending flag EWMA (repair pressure)."""
        allocated = [pid for pid in range(self.capacity_pages)
                     if self._storage[pid] is not None]
        ranked = sorted(allocated,
                        key=lambda pid: (self._scanned[pid],
                                         -self._flag_ewma[pid], pid))
        return ranked[:top] if top is not None else ranked

    def scrub(self, *, max_pages: int | None = None, now: int = 0,
              min_age: int = 0, prioritize: bool = False,
              coalesce: bool = True) -> dict:
        """Incrementally sweep allocated pages: scan, repair flagged words,
        write back, attributing repairs to each page's owner.

        A persistent round-robin cursor spreads work across calls;
        `max_pages` caps this call's sweep (the engine interleaves small
        sweeps between decode steps), and `min_age` skips pages touched
        within the last `min_age` steps of `now` — hot pages are about to be
        read (and so corrected) anyway.

        `prioritize=True` replaces the round-robin order with `hot_pages()`:
        never-scanned pages first, then pages by descending scan-flag EWMA,
        so a small `max_pages` budget lands on the pages that have actually
        been flagging (the estimator-driven schedule the serving engine
        uses) instead of whatever the cursor reaches next.

        `coalesce=True` (default) runs the repair pipeline: every in-budget
        page's scan is dispatched before any mask is pulled (one sync per
        sweep), and all tenants' flagged rows coalesce through the shared
        `RepairQueue` into one bucketed drain — the multi-tenant engine's
        background scrub amortizes one drain per step. `coalesce=False`
        keeps the per-page scan→whole-page-decode baseline (bit-identical
        repairs and identical per-owner attribution)."""
        allocated = [pid for pid in range(self.capacity_pages)
                     if self._storage[pid] is not None]
        if not allocated:
            return {"pages": 0, "flagged_words": 0, "repaired_words": 0,
                    "by_owner": {}}
        budget = len(allocated) if max_pages is None else max_pages
        if prioritize:
            order = self.hot_pages()
        else:
            # rotate so the sweep resumes where the previous call stopped
            start = next((j for j, pid in enumerate(allocated)
                          if pid >= self._scrub_cursor), 0)
            order = allocated[start:] + allocated[:start]
        # budget/age selection is identical for both sweep flavors (and
        # independent of scan results), so resolve it up front
        selected: list[int] = []
        for pid in order:
            if len(selected) >= budget:
                break
            if now - self._stamp[pid] < min_age:
                continue
            selected.append(pid)
            if not prioritize:
                self._scrub_cursor = pid + 1
        if self._scrub_cursor >= self.capacity_pages:
            self._scrub_cursor = 0
        if coalesce:
            swept, flagged_words, repaired, by_owner = \
                self._scrub_selected_coalesced(selected)
        else:
            swept, flagged_words, repaired, by_owner = \
                self._scrub_selected_baseline(selected)
        self.stats.scrub_rounds += 1
        self.stats.scrub_words += swept * self.page_words
        self.stats.scrub_corrected += repaired
        self.stats.scrub_uncorrectable += flagged_words - repaired
        reg = obs_metrics.current()
        if reg.enabled:
            reg.counter("pool_scrub_pages", layer="pool").inc(swept)
            reg.counter("pool_scrub_flagged", layer="pool").inc(flagged_words)
            reg.counter("pool_scrub_repaired", layer="pool").inc(repaired)
        for owner, ent in by_owner.items():
            tot = self.scrub_by_owner.setdefault(
                owner, {"flagged_words": 0, "repaired_words": 0})
            tot["flagged_words"] += ent["flagged_words"]
            tot["repaired_words"] += ent["repaired_words"]
            if reg.enabled:
                lab = {"layer": "pool",
                       "tenant": str(owner) if owner is not None else ""}
                reg.counter("pool_scrub_flagged_by_owner", **lab).inc(
                    ent["flagged_words"])
                reg.counter("pool_scrub_repaired_by_owner", **lab).inc(
                    ent["repaired_words"])
        return {"pages": swept, "flagged_words": flagged_words,
                "repaired_words": repaired, "by_owner": by_owner}

    def _note_page_scan(self, pid: int, nf: int, est) -> object:
        """Post-scan bookkeeping shared by both sweep flavors: flag EWMA,
        scanned marker, estimator feed. Returns the page's owner."""
        a = self.flag_alpha if self._scanned[pid] else 1.0
        self._flag_ewma[pid] += a * (nf / self.page_words
                                     - self._flag_ewma[pid])
        self._scanned[pid] = True
        owner = self._owner[pid]
        if est.enabled:
            est.observe_scan(nf, self.page_words, n_symbols=self.code.n,
                             region=str(owner) if owner is not None else "")
        return owner

    def _scrub_selected_baseline(self, selected: list[int]):
        """Per-page sweep over the selected pids: sync each page's flag
        count, decode the whole page when any row flags."""
        scan = self._template._scanner()
        decode = self._template._decoder()
        est = obs_ras.current()
        flagged_words = repaired = 0
        by_owner: dict[object, dict] = {}
        for pid in selected:
            page = self._storage[pid]
            flags = scan(page)
            nf = int(jnp.sum(flags))
            owner = self._note_page_scan(pid, nf, est)
            if not nf:
                continue
            flagged_words += nf
            _y, res = decode(page)
            good = flags & ~res.detect_fail
            self._storage[pid] = jnp.where(good[:, None], res.symbols, page)
            ok = int(jnp.sum(good))
            repaired += ok
            if est.enabled:
                iters = getattr(res, "iterations", None)
                if iters is not None:
                    est.observe_decode(iters, self._template.n_iters,
                                       detect_fail=res.detect_fail,
                                       region=str(owner) if owner is not None
                                       else "")
            ent = by_owner.setdefault(
                owner, {"flagged_words": 0, "repaired_words": 0})
            ent["flagged_words"] += nf
            ent["repaired_words"] += ok
        return len(selected), flagged_words, repaired, by_owner

    def _scrub_selected_coalesced(self, selected: list[int]):
        """Pipelined sweep over the selected pids: every scan dispatched
        before one mask sync, flagged pages pulled whole in a second
        batched sync, flagged rows from every tenant's pages coalesced
        through the shared `RepairQueue`, one bucketed drain (which also
        feeds the estimator per owner region). Row slicing and repair
        writes happen on host page copies: every device op here is
        page-shaped or bucket-shaped, so sweeps reuse warm executables no
        matter how the flag counts vary (a per-flag-count gather/scatter
        would recompile on every new count)."""
        if not selected:
            return 0, 0, 0, {}
        scan = self._template._scanner()
        masks = jax.device_get(
            [scan(self._storage[pid]) for pid in selected])
        est = obs_ras.current()
        queue = self._template._repair_queue()
        flagged_words = 0
        flagged = []
        for pid, mask in zip(selected, masks, strict=True):
            rows = np.flatnonzero(mask)
            owner = self._note_page_scan(pid, int(rows.size), est)
            if rows.size:
                flagged.append((pid, rows, owner))
                flagged_words += int(rows.size)
        pages = jax.device_get([self._storage[pid]
                                for pid, _, _ in flagged])
        for (pid, rows, owner), arr in zip(flagged, pages, strict=True):
            arr = np.array(arr)        # device_get views can be read-only

            def writeback(syms, ok, pid=pid, rows=rows, arr=arr):
                good = rows[ok]
                if good.size:
                    arr[good] = syms[ok].astype(arr.dtype)
                    self._storage[pid] = jnp.asarray(arr, jnp.int32)

            queue.enqueue(arr[rows], writeback, owner=owner,
                          provenance=("pool", pid, rows))
        rep = queue.drain()
        by_owner = {owner: dict(ent)
                    for owner, ent in rep["by_owner"].items()}
        return len(selected), flagged_words, rep["repaired"], by_owner

    # -- fault injection over the whole pool --------------------------------

    def inject(self, channel, key: int | jax.Array, *, t: float = 0.0,
               n_reads: int = 0, owners=None, exact: int = 0) -> int:
        """Corrupt allocated pool pages in place through a level-domain
        channel (optionally only pages owned by `owners`). Returns cells
        changed. Shared pages are corrupted once — exactly like one physical
        page going bad under every alias. `exact=m` corrupts exactly m cells
        of every word instead of sampling the channel per cell."""
        if channel.domain != "level":
            raise ValueError(f"{type(channel).__name__} is an integer-domain "
                             "channel; stored cells need a level-domain one")
        if channel.p != self.code.p:
            raise ValueError(f"channel alphabet {channel.p} != "
                             f"GF({self.code.p})")
        if isinstance(key, int):
            key = jax.random.PRNGKey(key)
        want = None if owners is None else set(owners)
        changed = 0
        for pid in range(self.capacity_pages):
            page = self._storage[pid]
            if page is None:
                continue
            if want is not None and self._owner[pid] not in want:
                continue
            k = jax.random.fold_in(key, pid)
            new = apply_faults(channel, k, page, exact, t=t, n_reads=n_reads)
            new = new.astype(jnp.int32)
            changed += int(jnp.sum(new != page))
            self._storage[pid] = new
        return changed


class PooledStore(PagedProtectedStore):
    """A `PagedProtectedStore` whose pages live in a shared
    `ProtectedPagePool`, addressed through a per-tenant block table.

    Storage semantics match the standalone store exactly (the whole test
    suite's read/write/inject/scrub behavior carries over); what changes is
    where pages live: appends allocate from the pool, writes to an aliased
    page copy-on-write, and `free()` returns every block. Executables are
    the pool template's — one cached jit per shape for all tenants."""

    def __init__(self, pool: ProtectedPagePool, *, owner=None, key: int = 0):
        super().__init__(pool.code, page_words=pool.page_words,
                         mesh=pool.mesh, n_iters=pool._template.n_iters,
                         damping=pool._template.damping,
                         llv_scale=pool._template.llv_scale,
                         llv_mode=pool._template.llv_mode, key=key,
                         policy=pool.policy)
        self.pool = pool
        self.owner = owner
        self.block_table: list[int] = []
        self._pages = _BlockTableView(self)   # keep `_pages`-style debugging
                                              # (tests poke st._pages[i])

    # -- storage indirection over the pool ----------------------------------

    @property
    def n_pages(self) -> int:
        return len(self.block_table)

    def page(self, i: int) -> jnp.ndarray:
        return self.pool.page(self.block_table[i])

    def _set_page(self, i: int, page: jnp.ndarray) -> None:
        pid = self.block_table[i]
        if self.pool.refcount(pid) > 1:
            # copy-on-write: writing through an aliased block must never be
            # visible to the other tenants holding it
            new_pid = self.pool.alloc(self.owner)
            self.pool.set_page(new_pid, page)
            self.pool._stamp[new_pid] = self.pool._stamp[pid]
            self.pool.free(pid)
            self.block_table[i] = new_pid
        else:
            self.pool.set_page(pid, page)

    def _append_page(self) -> None:
        self.block_table.append(self.pool.alloc(self.owner))

    def _iter_pages(self) -> Iterator[jnp.ndarray]:
        for i in range(self.n_pages):
            yield self.page(i)

    def free(self) -> None:
        for pid in self.block_table:
            self.pool.free(pid)
        self.block_table.clear()
        self._n_words = 0

    def fork(self, owner=None) -> "PooledStore":
        """Clone this store by aliasing every block (prefix sharing): no
        pages are copied until either side writes (copy-on-write)."""
        clone = PooledStore(self.pool, owner=owner)
        for pid in self.block_table:
            self.pool.ref(pid)
            clone.block_table.append(pid)
        clone._n_words = self._n_words
        return clone

    # -- capacity preflight --------------------------------------------------

    def pages_needed(self, m: int) -> int:
        """Worst-case fresh pool pages an `append_words(m rows)` will take:
        new trailing pages plus one CoW copy if the current tail block is
        aliased and partially filled."""
        pw = self.page_words
        slot = self._n_words % pw
        new_pages = -(-(self._n_words + m) // pw) - self.n_pages
        cow = int(slot != 0 and self.block_table
                  and self.pool.refcount(self.block_table[-1]) > 1)
        return max(new_pages, 0) + cow

    def append_words(self, u):
        u = jnp.asarray(u)
        if u.ndim == 2 and u.shape[1] == self.code.k:
            need = self.pages_needed(int(u.shape[0]))
            if need > self.pool.available:
                raise PoolExhausted(
                    f"append of {int(u.shape[0])} words needs {need} pool "
                    f"pages but only {self.pool.available} are free")
        return super().append_words(u)

    def append_encoded(self, enc):
        enc = jnp.asarray(enc, jnp.int32)
        if enc.ndim == 2 and enc.shape[1] == self.code.n:
            need = self.pages_needed(int(enc.shape[0]))
            if need > self.pool.available:
                raise PoolExhausted(
                    f"append of {int(enc.shape[0])} words needs {need} pool "
                    f"pages but only {self.pool.available} are free")
        return super().append_encoded(enc)

    # -- shared executables --------------------------------------------------

    def _encoder(self):
        return self.pool._template._encoder()

    def _scanner(self):
        return self.pool._template._scanner()

    def _decoder(self):
        return self.pool._template._decoder()

    def _repair_queue(self):
        # one shared queue (and one set of bucketed decode executables) for
        # every tenant — cross-tenant repairs coalesce into the same drain
        return self.pool._template._repair_queue()


class _BlockTableView:
    """List-like view of a PooledStore's pages so storage-level debugging
    idioms (`store._pages[i]`, `store._pages[i] = corrupted`) keep working
    against the pool-backed store."""

    def __init__(self, store: PooledStore):
        self._store = store

    def __len__(self) -> int:
        return self._store.n_pages

    def __getitem__(self, i: int) -> jnp.ndarray:
        return self._store.page(i)

    def __setitem__(self, i: int, page) -> None:
        self._store._set_page(i, jnp.asarray(page, jnp.int32))

    def __iter__(self):
        return self._store._iter_pages()

    def __bool__(self) -> bool:
        return self._store.n_pages > 0

    def clear(self) -> None:  # PagedProtectedStore.free() compatibility
        self._store.free()
