"""The memory configuration's pinned code tables, checked in plain numpy
rather than trusted: [I | P] spans exactly the null space of H over
GF(p), and the program runs that code."""
import json
import os

import numpy as np
import pytest

import _benchpath

CONFIG = os.path.join(_benchpath.BENCH, "configs", "nbldpc-mem-wl1024.json")


def _table(cfg: dict, key: str) -> np.ndarray:
    path = os.path.join(os.path.dirname(CONFIG), cfg[key])
    with open(path) as f:
        rows = [line.strip() for line in f if line.strip()]
    return np.array([[int(c, cfg["p"]) for c in r] for r in rows], np.int64)


def _rank_mod_p(a: np.ndarray, p: int) -> int:
    """Rank over GF(p), p prime, by Gaussian elimination."""
    a = a.copy() % p
    rank = 0
    for col in range(a.shape[1]):
        pivots = np.flatnonzero(a[rank:, col]) + rank
        if not pivots.size:
            continue
        a[[rank, pivots[0]]] = a[[pivots[0], rank]]
        a[rank] = a[rank] * pow(int(a[rank, col]), -1, p) % p
        others = np.flatnonzero(a[:, col])
        others = others[others != rank]
        a[others] = (a[others] - np.outer(a[others, col], a[rank])) % p
        rank += 1
        if rank == a.shape[0]:
            break
    return rank


@pytest.fixture(scope="module")
def tables():
    with open(CONFIG) as f:
        cfg = json.load(f)
    return cfg, _table(cfg, "generator_file"), _table(cfg, "parity_check_file")


def test_rank_mod_p():
    assert _rank_mod_p(np.array([[1, 2], [2, 1]]), 3) == 1
    assert _rank_mod_p(np.array([[1, 2], [1, 1]]), 3) == 2
    assert _rank_mod_p(np.zeros((2, 3), np.int64), 3) == 0


def test_generator_spans_the_parity_checks_null_space(tables):
    cfg, P, H = tables
    n, k, p = cfg["n"], cfg["k"], cfg["p"]
    assert P.shape == (k, n - k) and H.shape == (n - k, n)
    G = np.concatenate([np.eye(k, dtype=np.int64), P], axis=1)
    assert not (H @ G.T % p).any()
    # rank H = n - k: the code H defines has dimension k, so [I | P]
    # generates all of it and nothing else
    assert _rank_mod_p(H, p) == n - k


def test_program_runs_the_configured_code(tables):
    from repro.core import get_code
    cfg, P, H = tables
    code = get_code(cfg["code"])
    assert (code.p, code.n, code.k) == (cfg["p"], cfg["n"], cfg["k"])
    assert np.array_equal(np.asarray(code.P) % code.p, P)
    assert np.array_equal(np.asarray(code.H) % code.p, H)
