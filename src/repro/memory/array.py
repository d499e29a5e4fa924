"""`ProtectedMemoryArray`: NB-LDPC-protected tensor storage (memory mode).

This is the **host packing backend** of the protected-store stack: tensors
live as numpy codeword arrays, encode runs on the host BLAS path, and reads
decode synchronously under a controller policy. It is the right backend for
checkpoints and cold storage; live serving workloads use the device-resident
`repro.memory.paged.PagedProtectedStore`, which keeps pages as jax arrays,
encodes on device, and streams corrected reads so decode overlaps the
consumer.

Arbitrary tensors are packed into GF(p) codewords on write — bytes are
symbolized as base-p digits (6 trits/byte for GF(3), vs the 8 binary-valued
trits/byte of the original checkpoint hack: 25% fewer cells; see
`repro.memory.packing`, shared with the device backend) and encoded with the
framework's own systematic code — and decoded on read through the vectorized
`repro.core.decode` engine, under a pluggable controller policy
(`repro.memory.controller`). Device faults are injected through the
`repro.memory.channel` models, never by hand-editing stored words.

    mem = ProtectedMemoryArray(code="wl1024_r08", controller="writeback")
    mem.write("kv", kv_cache)
    mem.inject(asymmetric_adjacent(3, 1e-3, 5e-4), key=0)
    kv = mem.read("kv")                   # corrected transparently
    mem.controller.stats.corrected       # accounting per policy
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import get_code, np_encode_words
from repro.core.construction import LDPCCode

from .channel import Channel, apply_faults
from .controller import MemoryController, make_controller
from .packing import digits_per_byte, symbolize_bytes, desymbolize_bytes

__all__ = ["ProtectedMemoryArray", "StoredTensor", "symbolize_bytes",
           "desymbolize_bytes", "digits_per_byte"]


@dataclasses.dataclass
class StoredTensor:
    """One tensor's protected representation: (n_words, n) cell levels."""

    enc: np.ndarray                # (n_words, n) levels in [0, p), int8
    dtype: str
    shape: tuple
    nbytes: int


class ProtectedMemoryArray:
    """A named store of tensors held as NB-LDPC codewords of one code."""

    def __init__(self, code: str | LDPCCode = "wl1024_r08", *,
                 controller: str | MemoryController | None = "basic",
                 channel: Channel | None = None, key: int = 0, **ctrl_kw):
        self.code = get_code(code) if isinstance(code, str) else code
        self.controller = make_controller(controller, **ctrl_kw)
        self.channel = channel
        self._store: dict[str, StoredTensor] = {}
        self._key = jax.random.PRNGKey(key)
        self._injections = 0

    # -- introspection ------------------------------------------------------

    @property
    def names(self):
        return sorted(self._store)

    @property
    def stats(self):
        return self.controller.stats

    def stored(self, name: str) -> StoredTensor:
        return self._store[name]

    def import_stored(self, name: str, st: StoredTensor) -> None:
        """Adopt an externally persisted protected tensor (checkpoint
        restore path) without re-encoding."""
        self._store[name] = StoredTensor(
            np.asarray(st.enc, np.int8), str(st.dtype), tuple(st.shape),
            int(st.nbytes))

    def discard(self, name: str) -> None:
        """Drop a tensor's stored codewords (streaming save/restore keeps
        one leaf resident at a time instead of the whole checkpoint)."""
        self._store.pop(name, None)

    def n_words(self) -> int:
        return sum(st.enc.shape[0] for st in self._store.values())

    # -- write / read -------------------------------------------------------

    def write(self, name: str, array) -> StoredTensor:
        arr = np.asarray(array)
        raw = arr.tobytes()
        code = self.code
        syms = symbolize_bytes(raw, code.p)
        pad = (-syms.size) % code.k
        words = np.pad(syms, (0, pad)).reshape(-1, code.k)
        enc = np_encode_words(words, code).astype(np.int8)
        st = StoredTensor(enc, str(arr.dtype), arr.shape, len(raw))
        self._store[name] = st
        self.controller.note_write(enc.shape[0])
        self.controller.tick(code, self._store)
        return st

    def read(self, name: str, *, correct: bool = True) -> np.ndarray:
        st = self._store[name]
        if correct:
            levels = self.controller.read(self.code, self._store, name)
        else:
            levels = st.enc.astype(np.int64) % self.code.p
        syms = levels[:, :self.code.k].reshape(-1)
        raw = desymbolize_bytes(syms, st.nbytes, self.code.p)
        # frombuffer over `bytes` is a read-only view; hand back a writable
        # copy so callers can mutate what they read (it's their tensor).
        arr = np.frombuffer(raw, dtype=np.dtype(st.dtype)).reshape(st.shape)
        out = arr.copy()
        self.controller.tick(self.code, self._store)
        return out

    # -- fault injection / maintenance --------------------------------------

    def inject(self, channel: Channel | None = None,
               key: int | jax.Array | None = None, *, t: float = 0.0,
               n_reads: int = 0, exact: int = 0) -> int:
        """Corrupt the stored words in place through a channel model. `key`
        is a PRNG key or a plain int seed. Returns the number of cells
        actually changed. Each call folds a fresh sub-key, so repeated
        injections accumulate (aging). `exact=m` corrupts exactly m cells
        of every word (`Channel.corrupt_exact`) instead of sampling the
        channel per cell — a known error count per word."""
        ch = channel if channel is not None else self.channel
        if ch is None:
            raise ValueError("no channel: pass one or construct the array "
                             "with channel=...")
        if ch.domain != "level":
            raise ValueError(f"{type(ch).__name__} is an integer-domain "
                             "channel; stored cells need a level-domain one")
        if ch.p != self.code.p:
            raise ValueError(f"channel alphabet {ch.p} != GF({self.code.p})")
        if key is None:
            key = jax.random.fold_in(self._key, self._injections)
        elif isinstance(key, int):
            key = jax.random.PRNGKey(key)
        self._injections += 1
        changed = 0
        for i, name in enumerate(self.names):
            st = self._store[name]
            k = jax.random.fold_in(key, i)
            words = jnp.asarray(st.enc, jnp.int32)
            new = np.asarray(apply_faults(ch, k, words, exact, t=t,  # noqa: RPL007 - fault-injection utility, not a hot path; storage is host numpy
                                          n_reads=n_reads), np.int8)
            changed += int((new != st.enc).sum())
            st.enc = new
        return changed

    def iter_pages(self, page_words: int | None = None):
        """Writable (b, n) pages over the stored words (`page_words` rows
        per page; one page per tensor when None) — the streaming surface
        for `scrub_pages` and external scrub services."""
        return self.controller.iter_pages(self._store, page_words)

    def scrub(self, *, page_words: int | None = None, **kw) -> dict:
        """Explicit full sweep (any policy): scan + repair storage.
        `page_words` streams the sweep in fixed-size pages (incremental
        scrubbing for arrays larger than device memory). Extra keywords
        (`coalesce=`, `scan_ahead=`, `drain_words=`) reach
        `MemoryController.scrub_pages` — the coalescing repair pipeline is
        the default; `coalesce=False` keeps the per-page baseline."""
        return self.controller.scrub(self.code, self._store,
                                     page_words=page_words, **kw)

    def scrub_pages(self, pages, **kw) -> dict:
        """Sweep an explicit page iterator (see `iter_pages`) — the hook
        for scrubbing external storage through this array's code and
        controller."""
        return self.controller.scrub_pages(self.code, pages, **kw)
