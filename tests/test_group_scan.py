"""Grouped syndrome scan of the coalesced scrub.

A coalesced scrub scans `SCAN_GROUP` pages per launch: the pages of a group
are stacked row-wise into one scan, and the last group is padded up to a
power of two with repeats of one of its pages, whose masks are dropped. The
masks must be bit-identical to the per-page scan, the scrubs must repair
exactly what the per-page baseline repairs, and a sweep's shapes must stay
fixed, so a warm sweep builds no executable.
"""
import io
import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import get_code, np_encode_words
from repro.memory import PagedProtectedStore, PooledStore, ProtectedPagePool
from repro.memory import paged

PW = 8                  # words a page
G = 8                   # pages a scan launch, in place of SCAN_GROUP
COUNTS = [1, 3, G - 1, G, G + 1, G + 3]   # pages a sweep covers


@pytest.fixture(scope="module")
def code():
    return get_code("wl160_r08")


@pytest.fixture(autouse=True)
def small_groups(monkeypatch):
    monkeypatch.setattr(paged, "SCAN_GROUP", G)


def _damaged(code, n_pages):
    """n_pages * PW codewords with one wrong symbol in the first row, the
    last row and a middle row of the sweep (so in the first group, the last
    group and, where there is one, the padded tail), plus the clean words."""
    rng = np.random.default_rng(n_pages)
    n = n_pages * PW
    enc = np_encode_words(rng.integers(0, code.p, (n, code.k)),
                          code).astype(np.int32)
    rows = np.unique([0, n // 2, n - 1])
    bad = enc.copy()
    cols = rng.integers(0, code.n, rows.size)
    bad[rows, cols] = (bad[rows, cols] + 1) % code.p
    return bad, enc, rows


@pytest.mark.parametrize("policy", ["ref", "interpret"])
@pytest.mark.parametrize("n_pages", COUNTS)
def test_group_masks_match_per_page_scan(code, n_pages, policy):
    bad, _clean, rows = _damaged(code, n_pages)
    st = PagedProtectedStore(code, page_words=PW, policy=policy)
    st.append_encoded(bad)
    pages = [st.page(i) for i in range(n_pages)]
    grouped = st._scan_masks(pages)
    scan = st._scanner()
    per_page = np.stack([np.asarray(scan(pg)) for pg in pages])
    assert grouped.dtype == np.bool_ and grouped.shape == (n_pages, PW)
    np.testing.assert_array_equal(grouped, per_page)
    np.testing.assert_array_equal(np.flatnonzero(grouped), rows)
    full, tail = divmod(n_pages, G)
    want = {G} if full else set()
    if tail:
        want.add(1 << (tail - 1).bit_length())   # the tail's padded width
    assert set(st._group_scan_fns) == want


@pytest.mark.parametrize("n_pages", COUNTS)
def test_store_scrub_matches_per_page_baseline(code, n_pages):
    bad, clean, rows = _damaged(code, n_pages)
    stores, reports = [], []
    for coalesce in (False, True):
        st = PagedProtectedStore(code, page_words=PW)
        st.append_encoded(bad)
        reports.append(st.scrub(coalesce=coalesce))
        stores.append(st)
    (st_b, st_c), (rb, rc) = stores, reports
    for key in ("pages", "flagged_words", "repaired_words"):
        assert rb[key] == rc[key], (key, rb, rc)
    assert rc["flagged_words"] == rows.size
    np.testing.assert_array_equal(st_b.export_words(), st_c.export_words())
    np.testing.assert_array_equal(st_c.export_words(), clean)


@pytest.mark.parametrize("prioritize", [False, True])
@pytest.mark.parametrize("max_pages", [None, 3, G + 1])
def test_pool_scrub_matches_per_page_baseline(code, max_pages, prioritize):
    """Two tenants, 2G + 3 pages: each budgeted sweep flags, repairs and
    attributes what the per-page baseline does, sweep after sweep."""
    bad, clean, _rows = _damaged(code, 2 * G + 3)
    half = (G + 1) * PW

    def sweeps(coalesce):
        pool = ProtectedPagePool(code, page_words=PW, capacity_pages=2 * G + 4)
        a, b = PooledStore(pool, owner="a"), PooledStore(pool, owner="b")
        a.append_encoded(bad[:half])
        b.append_encoded(bad[half:])
        reps = [pool.scrub(max_pages=max_pages, prioritize=prioritize,
                           coalesce=coalesce) for _ in range(3)]
        return reps, np.concatenate([a.export_words(), b.export_words()])

    (rb, words_b), (rc, words_c) = sweeps(False), sweeps(True)
    for one_b, one_c in zip(rb, rc, strict=True):
        for key in ("pages", "flagged_words", "repaired_words", "by_owner"):
            assert one_b[key] == one_c[key], (key, one_b, one_c)
    np.testing.assert_array_equal(words_b, words_c)
    if max_pages is None or max_pages > G:
        np.testing.assert_array_equal(words_c, clean)


def test_pool_small_sweeps_build_power_of_two_groups(code):
    """Budgets 1 to 2G build only the group sizes 1, 2, 4, ..., G."""
    pool = ProtectedPagePool(code, page_words=PW, capacity_pages=2 * G)
    st = PooledStore(pool, owner="t")
    st.append_encoded(_damaged(code, 2 * G)[1])
    for budget in range(1, 2 * G + 1):
        assert pool.scrub(max_pages=budget)["pages"] == budget
    assert sorted(pool._template._group_scan_fns) == [
        1 << j for j in range(G.bit_length())]


def _compiles(fn):
    """Run `fn` and return the executables jax compiled while it ran."""
    buf = io.StringIO()
    handler = logging.StreamHandler(buf)
    logger = logging.getLogger("jax")
    logger.addHandler(handler)
    try:
        with jax.log_compiles(True):
            fn()
    finally:
        logger.removeHandler(handler)
    return [line for line in buf.getvalue().splitlines()
            if line.startswith("Compiling ")]


def test_warm_sweep_builds_no_executable(code):
    """Two sweeps of one store with different damage: the second compiles
    nothing (a full group and a padded tail, a decode and a writeback)."""
    bad, clean, _rows = _damaged(code, G + 3)
    st = PagedProtectedStore(code, page_words=PW)
    st.append_encoded(bad)
    assert _compiles(st.scrub)                   # the first sweep builds
    np.testing.assert_array_equal(st.export_words(), clean)
    rows = np.array([1, 2, 5 * PW + 3, len(clean) - 2])
    words = clean.copy()
    words[rows, 7] = (words[rows, 7] + 1) % code.p
    for i in sorted(set(rows // PW)):
        st._set_page(i, jnp.asarray(words[i * PW:(i + 1) * PW]))
    reports = []
    assert _compiles(lambda: reports.append(st.scrub())) == []
    assert reports[0]["flagged_words"] == reports[0]["repaired_words"] == 4
    np.testing.assert_array_equal(st.export_words(), clean)
