"""Protected-memory subsystem: channel models, ProtectedMemoryArray,
controller policies, checkpoint integration, and the BER campaign engine."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import get_code
from repro.memory import (Compose, LevelTransition, PlusMinusOne,
                          ProtectedMemoryArray, ReadDisturb, RetentionDrift,
                          ScrubController, StuckAt, asymmetric_adjacent,
                          desymbolize_bytes, paper_schemes, run_campaign,
                          select_acceptance_row, symbolize_bytes,
                          uniform_flip)


# ---------------------------------------------------------------------------
# channels
# ---------------------------------------------------------------------------

LEVELS = jnp.asarray(np.random.default_rng(0).integers(0, 3, (32, 80)),
                     jnp.int32)


@pytest.mark.parametrize("ch", [
    uniform_flip(3, 0.05),
    asymmetric_adjacent(3, 0.04, 0.01),
    RetentionDrift(3, rate=1e-3, rest_level=0),
    ReadDisturb(3, per_read=1e-3),
    StuckAt(3, fraction=0.02, seed=11),
    Compose(asymmetric_adjacent(3, 0.02, 0.01), StuckAt(3, 0.01, seed=2)),
])
def test_channel_determinism_same_key_same_faults(ch):
    kw = dict(t=100.0, n_reads=50)
    a = np.asarray(ch.apply(jax.random.PRNGKey(42), LEVELS, **kw))
    b = np.asarray(ch.apply(jax.random.PRNGKey(42), LEVELS, **kw))
    assert (a == b).all()
    assert ((a >= 0) & (a < 3)).all()


def test_transition_matrix_row_stochasticity_validated():
    with pytest.raises(ValueError, match="sum to 1"):
        LevelTransition(np.array([[0.5, 0.4], [0.0, 1.0]]))
    with pytest.raises(ValueError, match="negative"):
        LevelTransition(np.array([[1.2, -0.2], [0.0, 1.0]]))
    with pytest.raises(ValueError, match="square"):
        LevelTransition(np.ones((2, 3)) / 3)
    # a valid matrix passes and reports its marginal error rate
    ch = uniform_flip(5, 0.1)
    assert ch.error_rate() == pytest.approx(0.1)


def test_retention_drift_grows_with_time_read_disturb_with_reads():
    drift = RetentionDrift(3, rate=1e-3, rest_level=0)
    assert drift.error_rate(t=0.0) == 0.0
    assert 0 < drift.error_rate(t=100.0) < drift.error_rate(t=2000.0)
    rd = ReadDisturb(3, per_read=1e-3)
    assert rd.error_rate(n_reads=0) == 0.0
    assert 0 < rd.error_rate(n_reads=10) < rd.error_rate(n_reads=1000)


def test_stuck_cells_are_persistent_across_keys():
    ch = StuckAt(3, fraction=0.05, stuck_level=1, seed=3)
    a = np.asarray(ch.apply(jax.random.PRNGKey(0), LEVELS))
    b = np.asarray(ch.apply(jax.random.PRNGKey(999), LEVELS))
    assert (a == b).all()                      # mask depends on seed, not key
    assert (a[a != np.asarray(LEVELS)] == 1).all()


def test_corrupt_exact_changes_exactly_m_cells():
    ch = asymmetric_adjacent(3, 0.04, 0.01)
    y = ch.corrupt_exact(jax.random.PRNGKey(5), LEVELS, 7)
    diffs = (np.asarray(y) != np.asarray(LEVELS)).sum(axis=1)
    assert (diffs == 7).all()


def test_plusminusone_is_integer_domain():
    ch = PlusMinusOne(0.5)
    y = jnp.zeros((8, 50), jnp.int32)
    out = np.asarray(ch.apply(jax.random.PRNGKey(0), y))
    assert set(np.unique(out)) <= {-1, 0, 1}
    exact = np.asarray(ch.corrupt_exact(jax.random.PRNGKey(1), y, 4))
    assert (np.abs(exact).sum(axis=1) == 4).all()


def test_compose_validates_alphabets():
    with pytest.raises(ValueError, match="mixed"):
        Compose(uniform_flip(3, 0.1), uniform_flip(5, 0.1))


# ---------------------------------------------------------------------------
# symbolization
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("p", [3, 5, 7])
def test_symbolize_roundtrip(p, rng):
    raw = rng.integers(0, 256, 513, np.uint8).tobytes()
    syms = symbolize_bytes(raw, p)
    assert syms.min() >= 0 and syms.max() < p
    assert desymbolize_bytes(syms, 513, p) == raw


# ---------------------------------------------------------------------------
# array + controller policies
# ---------------------------------------------------------------------------

def _array(ctrl, **kw):
    # note: `policy=` in **kw is the controller's KernelPolicy, distinct
    # from the controller-policy NAME passed positionally
    return ProtectedMemoryArray("wl80_r08", controller=ctrl,
                                chunk_size=64, **kw)


@pytest.mark.parametrize("policy", ["basic", "writeback", "scrub"])
def test_write_corrupt_read_roundtrip_exact(policy, rng):
    mem = _array(policy)
    if policy == "scrub":
        mem.controller.interval = 10 ** 9            # no auto-sweeps here
    t = rng.normal(size=(24, 12)).astype(np.float32)
    mem.write("t", t)
    # one error in every word: wl80_r08 corrects any single symbol error,
    # whereas a per-cell channel draw can put 3 errors into one word
    mem.inject(asymmetric_adjacent(3, 3e-3, 1e-3), key=jax.random.PRNGKey(0),
               exact=1)
    out = mem.read("t")
    assert np.array_equal(out, t)
    assert out.dtype == t.dtype
    detected_first = mem.stats.detected
    assert detected_first > 0
    assert mem.stats.corrected == detected_first
    assert mem.stats.uncorrectable == 0

    out2 = mem.read("t")                             # storage not re-corrupted
    assert np.array_equal(out2, t)
    redetected = mem.stats.detected - detected_first
    if policy == "basic":
        assert redetected == detected_first          # latent errors remain
        assert mem.stats.writebacks == 0
    else:
        assert redetected == 0                       # reads repaired storage
        assert mem.stats.writebacks == detected_first


def test_scrub_counters_and_repair(rng):
    mem = _array("writeback")
    a = rng.normal(size=(16, 8)).astype(np.float32)
    b = rng.integers(0, 1000, 64).astype(np.int32)
    mem.write("a", a)
    mem.write("b", b)
    total_words = mem.n_words()
    mem.inject(uniform_flip(3, 2e-3), key=jax.random.PRNGKey(1))

    report = mem.scrub()
    assert report["words_scanned"] == total_words
    assert report["corrected"] == report["flagged"] > 0
    assert report["uncorrectable"] == 0
    st = mem.stats
    assert st.scrub_rounds == 1
    assert st.scrub_words == total_words
    assert st.scrub_cells == total_words * mem.code.n
    assert st.scrub_corrected == report["corrected"]
    assert st.scrub_bandwidth_cells_per_s > 0

    # the sweep repaired storage: a clean re-scan flags nothing
    report2 = mem.scrub()
    assert report2["flagged"] == 0
    assert np.array_equal(mem.read("a"), a)
    assert np.array_equal(mem.read("b"), b)


def test_scrub_policy_autosweeps_on_interval(rng):
    mem = _array("scrub", use_sharded=False)
    mem.controller.interval = 2
    mem.write("x", rng.normal(size=(8, 4)).astype(np.float32))   # op 1
    mem.inject(uniform_flip(3, 5e-3), key=jax.random.PRNGKey(2))
    assert mem.stats.scrub_rounds == 0
    mem.read("x")                                                # op 2 -> sweep
    assert mem.stats.scrub_rounds == 1
    assert isinstance(mem.controller, ScrubController)


def test_uncorrectable_words_are_counted(rng):
    mem = _array("basic")
    mem.write("x", rng.normal(size=(32, 16)).astype(np.float32))
    # far beyond the code's strength: most words must fail to decode
    mem.inject(uniform_flip(3, 0.4), key=jax.random.PRNGKey(3))
    mem.read("x")
    assert mem.stats.uncorrectable > 0


def test_integer_channel_rejected_for_storage(rng):
    mem = _array("basic")
    mem.write("x", rng.normal(size=(4, 4)).astype(np.float32))
    with pytest.raises(ValueError, match="integer-domain"):
        mem.inject(PlusMinusOne(0.1))
    with pytest.raises(ValueError, match="alphabet"):
        mem.inject(uniform_flip(5, 0.1))


def test_import_export_stored_roundtrip(rng):
    src = _array("basic")
    t = rng.normal(size=(6, 6)).astype(np.float64)
    st = src.write("t", t)
    dst = _array("basic")
    dst.import_stored("t", st)
    assert np.array_equal(dst.read("t"), t)


def test_read_returns_writable_array(rng):
    """Regression: reads came back as read-only frombuffer views, so callers
    mutating a corrected read crashed."""
    mem = _array("basic")
    t = rng.normal(size=(6, 5)).astype(np.float32)
    mem.write("t", t)
    out = mem.read("t")
    assert out.flags.writeable
    out[0, 0] = 123.0                              # must not raise
    assert np.array_equal(mem.read("t"), t)        # storage untouched


# ---------------------------------------------------------------------------
# scrub engine: device scan backend + paged sweeps
# ---------------------------------------------------------------------------

from repro.core import CODE_REGISTRY, np_encode_words  # noqa: E402
from repro.kernels.backend import policy_from_scan_backend  # noqa: E402
from repro.memory.controller import MemoryController  # noqa: E402


def _corrupted_words(code, rng, n_words=24, n_clean=8):
    """(n_words, n) valid codewords with single-cell hits beyond n_clean."""
    w = rng.integers(0, code.p, (n_words, code.k))
    enc = np_encode_words(w, code).astype(np.int8)
    rows = np.arange(n_clean, n_words)
    cols = rng.integers(0, code.n, rows.size)
    enc[rows, cols] = (enc[rows, cols] + 1) % code.p
    return enc


@pytest.mark.parametrize("name", sorted(CODE_REGISTRY))
def test_device_scan_matches_host_scan_all_registry_codes(name, rng):
    """Acceptance: the fused Pallas scan's flagged mask is identical to the
    host BLAS scan on every registry code (GF(3)/GF(5)/GF(7))."""
    code = get_code(name)
    enc = _corrupted_words(code, rng)
    host = MemoryController(policy=policy_from_scan_backend("host"),
                            scan_block=16)
    dev = MemoryController(policy=policy_from_scan_backend("device"),
                           scan_block=16, use_sharded=False)
    mh = host._scan_syndromes(code, enc)
    md = dev._scan_syndromes(code, enc)
    np.testing.assert_array_equal(mh, md)
    assert not mh[:8].any() and mh[8:].all()       # scans also correct


def test_scan_backend_validated():
    # the legacy vocabulary lives on only in the converter; bad names still
    # fail loudly there, and the removed kwarg itself is a TypeError
    with pytest.raises(ValueError, match="scan_backend"):
        policy_from_scan_backend("gpu")
    with pytest.raises(TypeError, match="scan_backend"):
        MemoryController(scan_backend="host")  # noqa: RPL006  # asserts the kwarg removal


def test_page_words_validated(rng):
    """Regression: page_words <= 0 must raise eagerly, not silently sweep
    zero words (negative) or crash inside range() (zero)."""
    mem = _array("basic")
    mem.write("t", rng.normal(size=(4, 4)).astype(np.float32))
    for bad in (0, -1):
        with pytest.raises(ValueError, match="page_words"):
            mem.scrub(page_words=bad)


def test_page_stats_bounded(rng):
    """Sweeps past MAX_PAGE_STATS pages keep totals but cap the per-page
    list, so huge-archive sweeps stay one-page-resident."""
    from repro.memory import controller as ctl
    mem = _array("basic")
    mem.write("t", rng.normal(size=(600, 4)).astype(np.float32))
    n_words = mem.n_words()
    cap, ctl.MAX_PAGE_STATS = ctl.MAX_PAGE_STATS, 8
    try:
        rep = mem.scrub(page_words=2)
    finally:
        ctl.MAX_PAGE_STATS = cap
    assert rep["pages"] == -(-n_words // 2) > 8
    assert len(rep["page_stats"]) == 8
    assert rep["page_stats_truncated"]
    assert rep["words_scanned"] == n_words


def test_paged_scrub_matches_whole_array_scrub(rng):
    """Acceptance: paged sweeps give identical repair results to whole-array
    scrubs, and per-page stats sum to the sweep totals."""
    t = rng.normal(size=(128, 12)).astype(np.float32)
    repaired = {}
    for backend in ("host", "device"):
        for page_words in (None, 7):
            mem = _array("writeback", policy=policy_from_scan_backend(backend),
                         scan_block=32)
            mem.write("t", t)
            mem.inject(uniform_flip(3, 2e-3), key=jax.random.PRNGKey(4))
            rep = mem.scrub(page_words=page_words)
            assert rep["backend"] == backend
            assert rep["flagged"] == rep["corrected"] > 0
            if page_words is not None:
                assert rep["pages"] > 1
            for key in ("words", "flagged", "corrected", "uncorrectable"):
                total = rep["words_scanned"] if key == "words" else rep[key]
                assert sum(pg[key] for pg in rep["page_stats"]) == total
            assert np.array_equal(mem.read("t"), t)
            repaired[(backend, page_words)] = mem.stored("t").enc.copy()
    ref = repaired[("host", None)]
    assert all(np.array_equal(ref, enc) for enc in repaired.values())


def test_scrub_pages_accepts_external_page_iterator(rng):
    """The paged API scrubs any iterator of writable (b, n) pages — not just
    this array's store (the cold-storage-service surface)."""
    mem = _array("basic", policy=policy_from_scan_backend("host"))
    code = mem.code
    w = rng.integers(0, code.p, (40, code.k))
    want = np_encode_words(w, code).astype(np.int8)
    enc = want.copy()
    rows = np.arange(10, 40)
    cols = rng.integers(0, code.n, rows.size)
    enc[rows, cols] = (enc[rows, cols] + 1) % code.p
    pages = [enc[lo:lo + 9] for lo in range(0, 40, 9)]
    rep = mem.scrub_pages(iter(pages))
    assert rep["pages"] == 5
    assert rep["flagged"] == rep["corrected"] == 30
    assert np.array_equal(enc, want)               # repaired through views


def test_big_field_scan_falls_back_to_exact_int64(rng):
    """Regression: n*(p-1)^2 >= 2^24 used to AssertionError. The int64
    fallback must flag nothing on valid GF(4099) codewords — the float32
    path provably misflags every one of them at this field size."""
    from repro.core import build_code
    code = build_code(64, 48, p=4099, dv=4, seed=0)
    assert code.n * (code.p - 1) ** 2 >= 2 ** 24
    w = rng.integers(0, code.p, (32, code.k))
    enc = np_encode_words(w, code)
    f32 = (enc.astype(np.float32) @ code.H.T.astype(np.float32))
    assert np.any(f32.astype(np.int64) % code.p != 0)   # f32 IS inexact here
    host = MemoryController(policy=policy_from_scan_backend("host"),
                            use_sharded=False)
    assert not host._scan_syndromes(code, enc).any()
    enc[:, 0] = (enc[:, 0] + 1) % code.p
    assert host._scan_syndromes(code, enc).all()


def test_big_field_device_backend_routes_to_exact_host_scan(rng):
    """The fused kernel accumulates in int32; codes past its 2^31 bound must
    route the device backend to the exact host path instead of silently
    wrapping."""
    from repro.core import build_code
    code = build_code(48, 40, p=8191, dv=4, seed=0)
    assert code.n * (code.p - 1) ** 2 >= 2 ** 31
    w = rng.integers(0, code.p, (16, code.k))
    enc = np_encode_words(w, code)
    dev = MemoryController(policy=policy_from_scan_backend("device"),
                           use_sharded=False)
    assert dev._scan_route(code) == "host"          # routed past the kernel
    assert not dev._scan_syndromes(code, enc).any()
    # reports must label the backend that actually ran, not the config
    # (clean pages: GF(8191) decode would build a (p, p) conv table)
    assert dev.scrub_pages(code, iter([enc]))["backend"] == "host"
    enc[:, 3] = (enc[:, 3] + 1) % code.p
    assert dev._scan_syndromes(code, enc).all()


# ---------------------------------------------------------------------------
# checkpoint integration
# ---------------------------------------------------------------------------

def test_protected_checkpoint_survives_channel_faults(tmp_path, rng):
    from repro import checkpoint as ckpt
    tree = {"w": rng.normal(size=(32, 32)).astype(np.float32),
            "b": rng.normal(size=(32,)).astype(np.float32)}
    ckpt.save_checkpoint(str(tmp_path), 7, tree, protect=True)
    noise = Compose(asymmetric_adjacent(3, 2e-3, 1e-3),
                    StuckAt(3, 1e-4, seed=5))
    assert ckpt.inject_storage_faults(str(tmp_path), noise, key=0) > 0
    out, man = ckpt.restore_checkpoint(str(tmp_path), tree)
    assert all(np.array_equal(out[k], tree[k]) for k in tree)
    cs = man["correction_stats"]
    assert cs["corrected"] == cs["detected"] > 0
    assert cs["uncorrectable"] == 0


def test_protected_checkpoint_version_guard(tmp_path, rng):
    import json
    import os
    from repro import checkpoint as ckpt
    tree = {"w": rng.normal(size=(4, 4)).astype(np.float32)}
    d = ckpt.save_checkpoint(str(tmp_path), 1, tree, protect=True)
    mf = os.path.join(d, "manifest.json")
    with open(mf) as f:
        man = json.load(f)
    man["prot_version"] = 1
    with open(mf, "w") as f:
        json.dump(man, f)
    with pytest.raises(OSError, match="format"):
        ckpt.restore_checkpoint(str(tmp_path), tree)


# ---------------------------------------------------------------------------
# BER campaign engine
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_campaign_reproduces_paper_style_comparison():
    """Scaled-down acceptance check (the full wl1024 table is produced by
    benchmarks/bench_memory_mode.py): at a raw BER where Hamming SECDED has
    saturated, NB-LDPC still improves >= 10x over unprotected."""
    code = get_code("wl256_r08")
    out = run_campaign(paper_schemes(code), [2e-2, 1e-2, 1e-3, 1e-4],
                       trials=24, hamming_trials=512, seed=0)
    rows = out["rows"]
    by = {(r["scheme"], r["raw_ber"]): r for r in rows}
    # Hamming helps at low raw BER but saturates by 1e-2
    assert by[("hamming_secded", 1e-4)]["improvement"] > 50
    assert by[("hamming_secded", 1e-2)]["improvement"] < 3
    # the modulo checksum is detect-only in memory mode
    assert by[("modulo_parity", 1e-3)]["improvement"] == pytest.approx(1.0)
    acc = select_acceptance_row(rows)
    assert acc is not None
    assert acc["nbldpc_improvement"] >= 10.0


@pytest.mark.slow
def test_campaign_runs_level_domain_channels():
    """Any-channel support: the same engine runs an MLC level-transition
    channel instead of the ±1 integer channel."""
    from repro.memory import NBLDPCScheme
    code = get_code("wl80_r08")
    sch = NBLDPCScheme(code, asymmetric_adjacent(3, 0.7, 0.3), n_iters=8)
    r_word, r_info = sch.residuals_at(1, trials=16, seed=0)
    assert r_word == 0.0                        # single error always fixed
    r_word8, _ = sch.residuals_at(16, trials=16, seed=0)
    assert r_word8 > 0.0                        # way past the strength


@pytest.mark.slow
def test_ber_common_shim_and_info_residuals():
    from benchmarks.ber_common import ber_curve, ber_curves
    code = get_code("wl80_r08")
    curve, r = ber_curve(code, [1e-3, 1e-4], trials=16, max_errors=6)
    assert set(curve) == {1e-3, 1e-4}
    assert len(r) == 7
    curves, prof = ber_curves(code, [1e-3], trials=16, max_errors=6)
    assert curves["info"][1e-3] <= curves["word"][1e-3] * 1.5
    assert prof.n_info == code.k
