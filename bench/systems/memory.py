"""System driver: a `PagedProtectedStore` patrolled by back-to-back scrubs.

The configuration names the code, the page size and the number of words.
Set-up fills the store with info words made on the device from the seed
(in chunks) through the store's own encode path, then warms one sweep.
The window runs whole sweeps: before each, the mix's damage plan puts
exactly `errors_per_word` wrong symbols into a seeded `share` of the
words (the cells drifting), then `scrub()` scans every page, decodes the
flagged words and writes them back.

The reference shares no code with the program: the info words are drawn
again from the seed, encoded in plain `jnp` with the generator matrix
that the configuration pins (`generator_file`), and every stored word
must equal its codeword after the window. Each sweep must also flag and
repair exactly the words that were damaged before it. The pinned tables
were written from the program's `wl1024_r08` construction; a plain numpy
test checks that they form the code (H [I | P]^T = 0 mod p, rank H =
n - k), and set-up refuses a program whose code is another.
"""
from __future__ import annotations

import functools
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from lib import traffic
from lib.harness import check

# served Pallas entry points; the harness fails the run if one of them was
# traced for the interpreter
KERNELS = {
    "encode_words": ("repro.kernels.gf_matmul", "gf_matmul_pallas"),
    "scan_syndromes": ("repro.kernels.gf_matmul", "scan_syndromes_pallas"),
}

CHUNK_WORDS = 65536          # info words made (and checked) per device call
HIT_PAGES = 64               # pages rewritten per damage call
HIT_ROWS = 8                 # damaged words per page per damage call
WARM_SWEEP = 1 << 30         # damage plan of the set-up sweep (never timed)


def load_generator(config: dict) -> np.ndarray:
    """The configuration's (k, n - k) generator matrix, base-p digits one
    row per line."""
    path = os.path.join(config["dir"], config["generator_file"])
    with open(path) as f:
        rows = [line.strip() for line in f if line.strip()]
    return np.array([[int(c, config["p"]) for c in r] for r in rows],
                    np.int32)


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _info(key, rows: int, k: int, p: int):
    return jax.random.randint(key, (rows, k), 0, p, jnp.int32)


def info_chunk(seed: int, c: int, rows: int, k: int, p: int):
    """Chunk `c` of the store's info words, made on the device."""
    return _info(traffic.jax_key(seed, 1, c), rows, k, p)


@functools.partial(jax.jit, static_argnums=(3,))
def _ref_mismatches(stored, u, P, p):
    """Words of `stored` (rows, n) that differ from [u | u P mod p]."""
    checks = jnp.matmul(u.astype(jnp.float32), P.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    checks = jnp.mod(checks, p).astype(jnp.int32)
    want = jnp.concatenate([u, checks], axis=1)
    return jnp.sum(jnp.any(stored != want, axis=1))


def _hit(pages, rows, cols, deltas, mask, p):
    """Add `deltas` (mod p) at (rows, cols) of each page where `mask`."""
    def one(pg, r, c, d, m):
        cur = pg[r[:, None], c]
        new = jnp.where(m[:, None], (cur + d) % p, cur)
        return pg.at[r[:, None], c].set(new)
    return [one(pg, rows[i], cols[i], deltas[i], mask[i])
            for i, pg in enumerate(pages)]


_hit_jit = jax.jit(_hit, static_argnums=(5,))


class System:
    def __init__(self, config: dict, mix: dict, seed: int):
        self.config, self.mix, self.seed = config, mix, int(seed)
        self.store = None
        self.sweeps: list[dict] = []

    # -- set-up -------------------------------------------------------------

    def setup(self) -> None:
        from repro.core import get_code
        from repro.memory import PagedProtectedStore
        cfg = self.config
        self.code = code = get_code(cfg["code"])
        if (code.p, code.n, code.k) != (cfg["p"], cfg["n"], cfg["k"]):
            raise ValueError(f"code {cfg['code']} is GF({code.p}) "
                             f"({code.n}, {code.k}), the configuration "
                             f"states GF({cfg['p']}) ({cfg['n']}, {cfg['k']})")
        self.P = load_generator(cfg)
        if not np.array_equal(np.asarray(code.P) % code.p, self.P):
            raise ValueError(f"the program's {cfg['code']} generator is not "
                             "the configuration's")
        dec = cfg["decoder"]
        self.store = PagedProtectedStore(
            code, page_words=cfg["page_words"], n_iters=dec["n_iters"],
            damping=dec["damping"])
        self.words = int(cfg["words"])
        self.chunk = min(CHUNK_WORDS, self.words)
        t0 = time.perf_counter()
        for c in range(self.words // self.chunk):
            self.store.append_words(
                info_chunk(self.seed, c, self.chunk, code.k, code.p))
        jax.block_until_ready(self.store.page(self.store.n_pages - 1))
        t1 = time.perf_counter()
        # warm every shape the window uses: one damage call and one sweep
        self._damage(WARM_SWEEP)
        self.store.scrub()
        jax.block_until_ready(self.store.page(self.store.n_pages - 1))
        print(f"bench: set-up: fill {t1 - t0:.1f} s, warm sweep "
              f"{time.perf_counter() - t1:.1f} s", file=sys.stderr)

    # -- the window ---------------------------------------------------------

    def _damage(self, sweep: int) -> int:
        """Apply the mix's damage plan for `sweep`; returns words hit."""
        code, pw = self.code, self.config["page_words"]
        words, cols, deltas = traffic.damage_plan(
            self.mix, self.seed, sweep, self.words, code.n, code.p)
        by_page: dict[int, list[int]] = {}
        for i, w in enumerate(words):
            by_page.setdefault(int(w) // pw, []).append(i)
        # one call per HIT_PAGES pages of HIT_ROWS rows each (a page with
        # more hits comes back in a later call); unhit filler pages keep
        # the call's shape fixed
        jobs = []
        for pid, idx in sorted(by_page.items()):
            for lo in range(0, len(idx), HIT_ROWS):
                jobs.append((pid, idx[lo:lo + HIT_ROWS]))
        e = cols.shape[1]
        hit_pages = min(HIT_PAGES, self.store.n_pages)
        while jobs:
            batch, rest, used = [], [], set()
            for job in jobs:
                if len(batch) < hit_pages and job[0] not in used:
                    batch.append(job)
                    used.add(job[0])
                else:
                    rest.append(job)
            jobs = rest
            filler = (pid for pid in range(self.store.n_pages)
                      if pid not in used)
            while len(batch) < hit_pages:
                batch.append((next(filler), []))
            rows = np.zeros((hit_pages, HIT_ROWS), np.int32)
            cc = np.zeros((hit_pages, HIT_ROWS, e), np.int32)
            dd = np.zeros((hit_pages, HIT_ROWS, e), np.int32)
            mask = np.zeros((hit_pages, HIT_ROWS), bool)
            for j, (pid, idx) in enumerate(batch):
                for r, i in enumerate(idx):
                    rows[j, r] = int(words[i]) % pw
                    cc[j, r] = cols[i]
                    dd[j, r] = deltas[i]
                    mask[j, r] = True
            new = _hit_jit([self.store.page(pid) for pid, _ in batch],
                           rows, cc, dd, mask, code.p)
            for (pid, _), pg in zip(batch, new, strict=True):
                self.store._set_page(pid, pg)
        return len(words)

    def run(self, seconds: float) -> dict:
        t0 = time.perf_counter()
        sweep = 0
        while True:
            with jax.profiler.TraceAnnotation("bench.damage"):
                placed = self._damage(sweep)
            ts = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.sweep"):
                rep = self.store.scrub()
            t = time.perf_counter()
            self.sweeps.append({
                "placed": placed, "pages": rep["pages"],
                "flagged": rep["flagged_words"],
                "repaired": rep["repaired_words"],
                "pad_rows": rep.get("drain", {}).get("pad_rows", 0),
                "dispatch_rows": rep.get("drain", {}).get("dispatch_rows", 0),
                "t0": ts, "t1": t})
            sweep += 1
            if t - t0 >= seconds:
                break
        pw = self.config["page_words"]
        swept = sum(s["pages"] for s in self.sweeps) * pw
        bad = sum(s["flagged"] != s["placed"] or s["repaired"] != s["placed"]
                  for s in self.sweeps)
        return {"t0": t0, "t1": t, "kind": "sweeps", "sweeps": self.sweeps,
                "words_swept": swept, "attempted": len(self.sweeps),
                "failed": bad,
                "work": {"scan_syndromes": {
                    "words": swept, "n": self.code.n, "p": self.code.p,
                    "nnz_h": int(np.count_nonzero(self.code.H))}}}

    # -- the check ----------------------------------------------------------

    def verify(self) -> list[dict]:
        """Every sweep flagged and repaired exactly the words damaged
        before it; every stored word equals its reference codeword."""
        flag_miss = sum(abs(s["flagged"] - s["placed"]) for s in self.sweeps)
        repair_miss = sum(abs(s["repaired"] - s["placed"])
                          for s in self.sweeps)
        store, code, pw = self.store, self.code, self.config["page_words"]
        P = jnp.asarray(self.P)
        per = self.chunk // pw
        bad = 0
        for c in range(self.words // self.chunk):
            stored = jnp.concatenate(
                [store.page(i) for i in range(c * per, (c + 1) * per)])
            u = info_chunk(self.seed, c, self.chunk, code.k, code.p)
            bad += int(_ref_mismatches(stored, u, P, code.p))
        self.store = None
        return [check("sweep_flag_misses", flag_miss, 0),
                check("sweep_repair_misses", repair_miss, 0),
                check("words_not_as_written", bad, 0)]
