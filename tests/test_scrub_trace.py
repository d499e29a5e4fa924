"""Spans and counters of the coalesced scrub pipeline.

A sweep of `PagedProtectedStore` (and of `ProtectedPagePool`) records one
`scrub.sweep` span whose children name each phase: the scan dispatch, the
mask pull, the flagged-page pull, and the repair queue's decode and
writeback. Their args count what each phase moved (the scan dispatch:
the group launches and the pages they cover), computed from shapes the
host already holds, so tracing adds no host sync.
"""
import jax
import numpy as np
import pytest

from repro import obs
from repro.core import get_code, np_encode_words
from repro.memory import PagedProtectedStore, PooledStore, ProtectedPagePool
from repro.memory import paged
from repro.obs import trace as obs_trace

PW = 16                 # words a page
PAGES = 6
G = 4                   # SCAN_GROUP here: one full group, a tail of 2
CHILDREN = ("scrub.scan_dispatch", "scrub.mask_pull", "scrub.page_pull",
            "repair.decode", "repair.writeback")


@pytest.fixture(scope="module")
def code():
    return get_code("wl160_r08")


@pytest.fixture
def small_groups(monkeypatch):
    monkeypatch.setattr(paged, "SCAN_GROUP", G)


def _damaged(code, rows):
    """PAGES * PW codewords with one wrong symbol in each of `rows`."""
    rng = np.random.default_rng(7)
    enc = np_encode_words(rng.integers(0, code.p, (PAGES * PW, code.k)),
                          code).astype(np.int32)
    bad = enc.copy()
    cols = rng.integers(0, code.n, len(rows))
    bad[rows, cols] = (bad[rows, cols] + 1) % code.p
    return bad, enc


# flagged rows: 3 in page 0, 1 in page 2, 1 in page 4 and all 16 of page
# 5, so 21 rows fill one 16-row decode bucket and part of a second
ROWS = np.array([0, 5, 9, 2 * PW + 4] + list(range(4 * PW + 15, 6 * PW)))


def _expected(code, rows):
    n, flagged_pages = code.n, len(set(int(r) // PW for r in rows))
    buckets = -(-len(rows) // PW)      # the store's queue: one PW-row bucket
    decode_bytes = buckets * PW * (n * 4 + n * 4 + 1 + 4)
    return {"scrub.scan_dispatch": {"dispatches": 2, "pages": PAGES},
            "scrub.mask_pull": {"bytes": PAGES * PW},
            "scrub.page_pull": {"bytes": flagged_pages * PW * n * 4},
            "repair.decode": {"dispatches": buckets, "bytes": decode_bytes},
            "repair.writeback": {"bytes": flagged_pages * PW * n * 4}}


def _check_tree(tr, want):
    spans = tr.spans()
    by_name = {}
    for e in spans:
        assert e["name"] not in by_name, f"two {e['name']} spans"
        by_name[e["name"]] = e
    assert set(by_name) == {"scrub.sweep", *CHILDREN}
    sweep = by_name["scrub.sweep"]["args"]
    assert sweep["parent"] is None and set(sweep) == {"id", "parent", "depth"}
    for child in CHILDREN:
        assert by_name[child]["args"]["parent"] == sweep["id"]
    for name, args in want.items():
        assert set(by_name[name]["args"]) == {*args, "id", "parent", "depth"}
        got = {k: by_name[name]["args"][k] for k in args}
        assert got == args, name


def test_store_sweep_records_one_tree_with_counts(code, small_groups):
    bad, clean = _damaged(code, ROWS)
    st = PagedProtectedStore(code, page_words=PW)
    st.append_encoded(bad)
    with obs.use_tracer(obs.Tracer()) as tr:
        rep = st.scrub()
    assert rep["flagged_words"] == rep["repaired_words"] == len(ROWS)
    assert "seconds" not in rep["drain"]
    _check_tree(tr, _expected(code, ROWS))
    np.testing.assert_array_equal(st.export_words(), clean)


def test_pool_sweep_emits_the_same_spans(code, small_groups):
    bad, clean = _damaged(code, ROWS)
    pool = ProtectedPagePool(code, page_words=PW, capacity_pages=PAGES + 2)
    a, b = PooledStore(pool, owner="a"), PooledStore(pool, owner="b")
    a.append_encoded(bad[:3 * PW])
    b.append_encoded(bad[3 * PW:])
    with obs.use_tracer(obs.Tracer()) as tr:
        rep = pool.scrub()
    assert rep["repaired_words"] == len(ROWS)
    _check_tree(tr, _expected(code, ROWS))
    np.testing.assert_array_equal(
        np.concatenate([a.export_words(), b.export_words()]), clean)


def test_clean_sweep_opens_no_repair_spans(code):
    _bad, clean = _damaged(code, ROWS)
    st = PagedProtectedStore(code, page_words=PW)
    st.append_encoded(clean)
    st.scrub()
    with obs.use_tracer(obs.Tracer()) as tr:
        st.scrub()
    assert {e["name"] for e in tr.spans()} == {
        "scrub.sweep", "scrub.scan_dispatch", "scrub.mask_pull",
        "scrub.page_pull"}
    assert tr.spans("scrub.page_pull")[0]["args"]["bytes"] == 0


def test_tracing_adds_no_host_sync(code, monkeypatch):
    """With and without a tracer a sweep makes the same `device_get` and
    `block_until_ready` calls; with none installed it records nothing."""
    calls = {"device_get": 0, "block_until_ready": 0}
    for name in calls:
        real = getattr(jax, name)

        def counted(*a, _real=real, _name=name, **kw):
            calls[_name] += 1
            return _real(*a, **kw)

        monkeypatch.setattr(jax, name, counted)

    def sweep(tracer):
        bad, _clean = _damaged(code, ROWS)
        st = PagedProtectedStore(code, page_words=PW)
        st.append_encoded(bad)
        st.scrub()                       # compiles outside the count
        st.append_encoded(bad)           # flagged rows for the counted one
        for k in calls:
            calls[k] = 0
        if tracer is None:
            st.scrub()
        else:
            with obs.use_tracer(tracer):
                st.scrub()
        return dict(calls)

    untraced = sweep(None)
    tr = obs.Tracer()
    assert sweep(tr) == untraced
    assert untraced["device_get"] == 3 and untraced["block_until_ready"] == 0
    assert len(tr.spans()) == 1 + len(CHILDREN)
    assert obs_trace.current() is obs_trace.NULL_TRACER
