"""Observability layer: metrics registry, span tracing, RAS estimators,
and the zero-cost-when-disabled contract of the instrumented hot paths."""
import json

import numpy as np
import pytest

from repro import obs
from repro.memory.channel import uniform_flip
from repro.obs import metrics as obs_metrics
from repro.obs import ras as obs_ras
from repro.obs import trace as obs_trace


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------


def test_registry_instruments_and_snapshot_roundtrip():
    reg = obs.MetricsRegistry()
    reg.counter("reads", layer="controller", tenant="a").inc(3)
    reg.counter("reads", layer="controller", tenant="a").inc(2)
    reg.gauge("slots", layer="engine").set(7)
    h = reg.histogram("lat", layer="engine")
    for v in (0.001, 0.003, 0.2):
        h.observe(v)
    snap = json.loads(json.dumps(reg.snapshot()))   # JSON-stable
    assert obs.MetricsRegistry.value(snap, "reads", tenant="a",
                                     layer="controller") == 5.0
    # label order must not matter: same series either way
    assert reg.counter("reads", tenant="a", layer="controller").value == 5.0
    assert obs.MetricsRegistry.value(snap, "slots", layer="engine") == 7.0
    hist = snap["lat"]["series"][0]
    assert hist["count"] == 3 and hist["sum"] == pytest.approx(0.204)
    assert hist["buckets"]["+Inf"] == 3                 # cumulative
    assert obs.MetricsRegistry.value(snap, "nope") is None


def test_registry_kind_mismatch_rejected():
    reg = obs.MetricsRegistry()
    reg.counter("x")
    with pytest.raises(ValueError, match="registered as counter"):
        reg.gauge("x")


def test_registry_label_cardinality_bounded():
    reg = obs.MetricsRegistry(max_series=4)
    for i in range(4):
        reg.counter("hits", tenant=str(i)).inc()
    with pytest.warns(RuntimeWarning, match="max_series"):
        reg.counter("hits", tenant="overflowing").inc()
    reg.counter("hits", tenant="another").inc()         # warns only once
    snap = reg.snapshot()
    assert len(snap["hits"]["series"]) == 5             # 4 real + overflow
    assert obs.MetricsRegistry.value(snap, "hits", overflow="true") == 2.0


def test_registry_exporters():
    reg = obs.MetricsRegistry()
    reg.counter("mem_detected", code="gf3n32").inc(4)
    reg.histogram("step_s").observe(0.01)
    text = reg.to_prometheus()
    assert '# TYPE mem_detected_total counter' in text
    assert 'mem_detected_total{code="gf3n32"} 4.0' in text
    assert 'step_s_bucket{le="0.01"} 1' in text
    assert "step_s_count 1" in text


def test_registry_append_jsonl(tmp_path):
    reg = obs.MetricsRegistry()
    reg.counter("c").inc()
    path = tmp_path / "m.jsonl"
    reg.append_jsonl(str(path), meta={"bench": "unit"})
    reg.append_jsonl(str(path))
    lines = path.read_text().splitlines()
    assert len(lines) == 2
    rec = json.loads(lines[0])
    assert rec["bench"] == "unit"
    assert rec["metrics"]["c"]["series"][0]["value"] == 1.0


# ---------------------------------------------------------------------------
# span tracing
# ---------------------------------------------------------------------------


def test_span_nesting_and_chrome_trace_export(tmp_path):
    tr = obs.Tracer()
    with obs.use_tracer(tr):
        with obs.span("outer", step=1) as sp:
            with obs.span("inner"):
                pass
            sp.set(tokens=4)
        tr.instant("mark", kind="preempt")
    path = tmp_path / "trace.json"
    doc = tr.to_chrome_trace(str(path))
    loaded = json.loads(path.read_text())
    assert loaded["traceEvents"] == doc["traceEvents"]

    inner, outer = tr.spans("inner")[0], tr.spans("outer")[0]
    # children close (and therefore record) before their parents; the
    # timestamps nest and depth, id and parent ride in args
    assert outer["args"]["depth"] == 0 and inner["args"]["depth"] == 1
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]
    assert outer["args"] == {"step": 1, "tokens": 4, "depth": 0,
                             "id": outer["args"]["id"], "parent": None}
    assert inner["args"]["parent"] == outer["args"]["id"]
    marks = [e for e in tr.events() if e["ph"] == "i"]
    assert marks and marks[0]["name"] == "mark"


def test_span_ids_link_children_to_parents_across_siblings():
    tr = obs.Tracer()
    with obs.use_tracer(tr):
        with obs.span("root"):
            with obs.span("a"):
                with obs.span("a1"):
                    pass
            with obs.span("b"):
                pass
        with obs.span("second_root"):
            pass
    ev = {e["name"]: e["args"] for e in tr.spans()}
    ids = [a["id"] for a in ev.values()]
    assert len(set(ids)) == len(ids) and all(isinstance(i, int) for i in ids)
    assert ev["root"]["parent"] is None
    assert ev["second_root"]["parent"] is None
    assert ev["a"]["parent"] == ev["b"]["parent"] == ev["root"]["id"]
    assert ev["a1"]["parent"] == ev["a"]["id"]
    assert [ev[n]["depth"] for n in ("root", "a", "a1", "b", "second_root")
            ] == [0, 1, 2, 1, 0]


def test_profiler_bridge_failure_propagates(monkeypatch):
    """A tracer that mirrors into the profiler and cannot annotate fails
    the span instead of quietly recording without annotations."""
    import jax.profiler

    class Broken:
        def __init__(self, name):
            raise RuntimeError("no annotations here")

    tr = obs.Tracer(jax_profiler=True)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Broken)
    with pytest.raises(RuntimeError, match="no annotations"):
        with tr.span("x"):
            pass
    assert tr.jax_profiler and not tr.spans()
    monkeypatch.undo()
    with tr.span("y"):                  # the failed span left no state
        pass
    assert tr.spans("y")[0]["args"]["depth"] == 0
    assert tr.spans("y")[0]["args"]["parent"] is None


def test_tracer_bounds_event_count():
    tr = obs.Tracer(max_events=3)
    for i in range(5):
        with tr.span(f"s{i}"):
            pass
    doc = tr.to_chrome_trace()
    assert len(doc["traceEvents"]) == 3
    assert doc["otherData"]["dropped_events"] == 2
    assert [e["name"] for e in doc["traceEvents"]] == ["s2", "s3", "s4"]


def test_span_disabled_is_shared_noop():
    assert obs_trace.current() is obs_trace.NULL_TRACER
    a = obs.span("anything", step=1)
    b = obs.span("else")
    assert a is b                       # one shared null span, no allocation
    with a as s:
        s.set(x=1)                      # no-op, no error


# ---------------------------------------------------------------------------
# RAS estimators
# ---------------------------------------------------------------------------


def test_ewma_converges_to_channel_flag_rate():
    """Feed scan observations drawn from a known LevelTransition channel;
    the flag-rate EWMA must converge to the closed-form expectation and the
    inverted raw BER to the channel's per-symbol error rate."""
    eps, n = 2e-3, 40
    ch = uniform_flip(3, eps)
    f_exp = obs_ras.expected_flag_rate(ch.T, n)
    est = obs.ErrorRateEstimator(alpha=0.05)
    rng = np.random.default_rng(0)
    words = 512
    for _ in range(400):
        flagged = int(rng.binomial(words, f_exp))
        est.observe_scan(flagged, words, n_symbols=n, region="bank0")
    r = est.region("bank0")
    assert r.flag_rate == pytest.approx(f_exp, rel=0.15)
    # eps is the per-symbol error prob (any wrong level), and raw_ber
    # inverts the word flag rate back to exactly that
    assert r.raw_ber() == pytest.approx(eps, rel=0.15)
    assert obs_ras.invert_flag_rate(f_exp, n) == pytest.approx(eps, rel=1e-6)


def test_estimator_stress_and_adaptive_interval():
    est = obs.ErrorRateEstimator(alpha=0.5, target_flag_rate=0.05)
    # clean region: interval stretches beyond nominal (capped by max_scale)
    for _ in range(8):
        est.observe_scan(0, 1024, region="cold")
    assert est.adaptive_interval(16, region="cold") > 16
    # hot region: flag rate far above target shrinks the interval
    for _ in range(8):
        est.observe_scan(512, 1024, region="hot")
        est.observe_decode([10, 10, 10], 10, detect_fail=[0, 0, 1],
                           region="hot")
    assert est.region("hot").stress == pytest.approx(1.0)
    assert est.adaptive_interval(16, region="hot") < 16
    assert est.hot_regions(1)[0][0] == "hot"
    # fleet-level pressure blends both; snapshot is JSON-stable
    json.dumps(est.snapshot())
    assert est.region("hot").residual_ber_proxy() > 0


def test_estimator_publish_to_registry():
    est = obs.ErrorRateEstimator(alpha=1.0)
    est.observe_scan(8, 64, n_symbols=32, region="t0")
    reg = obs.MetricsRegistry()
    est.publish(reg)
    snap = reg.snapshot()
    assert obs.MetricsRegistry.value(snap, "ras_flag_rate", layer="ras",
                                     region="t0") == pytest.approx(0.125)
    assert obs.MetricsRegistry.value(snap, "ras_raw_ber", layer="ras",
                                     region="t0") > 0


# ---------------------------------------------------------------------------
# disabled-path contract: telemetry off allocates nothing on hot paths
# ---------------------------------------------------------------------------


def test_disabled_hot_paths_allocate_no_instruments():
    """With no ambient registry/tracer/estimator, the instrumented read /
    scrub / decode paths must construct zero metric instruments and record
    zero events (the `.enabled` one-attribute-read contract)."""
    from repro.core import get_code, np_encode_words
    from repro.memory import PagedProtectedStore
    from repro.memory.controller import MemoryController

    assert obs_metrics.current() is obs_metrics.NULL_REGISTRY
    assert obs_ras.current() is obs_ras.NULL_ESTIMATOR

    rng = np.random.default_rng(0)
    code = get_code("wl32_r08")
    u = rng.integers(0, code.p, (12, code.k))
    st = PagedProtectedStore(code, page_words=8)
    st.append_words(u)
    ctl = MemoryController()
    enc = np_encode_words(u, code).astype(np.int8)

    before = obs.instrument_count()
    for i in range(st.n_pages):
        st.read_page_corrected(i)
    ctl.scrub_pages(code, iter([enc]))
    assert obs.instrument_count() == before
    # and the null sinks stayed empty
    assert obs_trace.current().events() == []
    assert obs_metrics.current().snapshot() == {}


def test_ambient_installers_nest_and_restore():
    reg, tr, est = (obs.MetricsRegistry(), obs.Tracer(),
                    obs.ErrorRateEstimator())
    with obs.use_metrics(reg), obs.use_tracer(tr), obs.use_estimator(est):
        assert obs_metrics.current() is reg
        assert obs_trace.current() is tr
        assert obs_ras.current() is est
        with obs.use_metrics() as inner:
            assert obs_metrics.current() is inner is not reg
        assert obs_metrics.current() is reg
    assert obs_metrics.current() is obs_metrics.NULL_REGISTRY
    assert obs_trace.current() is obs_trace.NULL_TRACER
    assert obs_ras.current() is obs_ras.NULL_ESTIMATOR


# ---------------------------------------------------------------------------
# ControllerStats dedup helpers (the engine's single banking path)
# ---------------------------------------------------------------------------


def test_controller_stats_merge_and_add_counts():
    from repro.memory.controller import ControllerStats
    a, b = ControllerStats(), ControllerStats()
    a.detected, a.corrected, a.words_read = 3, 2, 10
    b.detected, b.corrected, b.uncorrectable = 1, 1, 5
    out = ControllerStats().merge(a).merge(b)
    assert (out.detected, out.corrected, out.uncorrectable) == (4, 3, 5)
    assert out.words_read == 10
    assert a.correction_counts() == {"detected": 3, "corrected": 2,
                                     "uncorrectable": 0}
    # add_counts accepts both stats objects and plain dicts, and sums ONLY
    # the correction triple (scrub attribution has its own pool-side path)
    acc = dict.fromkeys(ControllerStats.CORRECTION_KEYS, 0)
    ControllerStats.add_counts(acc, a)
    ControllerStats.add_counts(acc, {"detected": 2, "scrub_flagged": 7})
    assert acc["detected"] == 5 and acc["corrected"] == 2
    assert "scrub_flagged" not in acc


def test_stats_publish_gauges_are_idempotent():
    from repro.memory.controller import ControllerStats
    s = ControllerStats()
    s.detected = 9
    reg = obs.MetricsRegistry()
    s.publish(reg, layer="pool")
    s.publish(reg, layer="pool")        # gauge-set, not counter-inc
    snap = reg.snapshot()
    assert obs.MetricsRegistry.value(snap, "controller_detected",
                                     layer="pool") == 9.0


# ---------------------------------------------------------------------------
# estimator-driven scrub prioritization (pool hot-page ordering)
# ---------------------------------------------------------------------------


def test_pool_prioritized_scrub_orders_by_flag_ewma():
    import jax
    import jax.numpy as jnp
    from repro.core import np_encode_words
    from repro.memory.pool import ProtectedPagePool

    pool = ProtectedPagePool("wl80_r08", page_words=8, capacity_pages=4)
    pids = [pool.alloc(owner=t) for t in ("a", "b", "c", "d")]
    rng = np.random.default_rng(1)
    code = pool.code
    for pid in pids:
        w = rng.integers(0, code.p, (8, code.k))
        pool.set_page(pid, jnp.asarray(np_encode_words(w, code), jnp.int32))
    # first sweep: every page scanned once, clean (EWMA baseline 0)
    pool.scrub()
    # exactly one wrong cell in every word of ONE page (always correctable)
    hot = pids[2]
    ch = uniform_flip(code.p, 0.02)
    pool.set_page(hot, ch.corrupt_exact(jax.random.PRNGKey(0),
                                        pool.page(hot), 1))
    est = obs.ErrorRateEstimator()
    with obs.use_estimator(est):
        rep = pool.scrub()                      # observes flags + repairs
    assert rep["flagged_words"] == rep["repaired_words"] == 8
    assert set(rep["by_owner"]) == {"c"}
    assert est.region("c").flag_rate == pytest.approx(1.0)
    # flag EWMA: 0 -> 0.3 * 1.0; the flagging page now ranks first
    assert pool.page_flag_rate(hot) == pytest.approx(0.3)
    assert pool.hot_pages(1) == [hot]
    # a 1-page prioritized sweep lands on the flagging page (now repaired,
    # so its EWMA decays by exactly 1 - flag_alpha), not the cursor's next
    rep1 = pool.scrub(max_pages=1, prioritize=True)
    assert rep1["pages"] == 1 and rep1["flagged_words"] == 0
    assert pool.page_flag_rate(hot) == pytest.approx(0.3 * 0.7)
    assert all(pool.page_flag_rate(p) == 0.0 for p in pids if p != hot)
