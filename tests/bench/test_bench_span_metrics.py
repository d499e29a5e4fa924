"""The scrub pipeline's per-layer metrics, read from the program's span
events: per-sweep phase times and counters on a synthetic trace."""
import itertools

import pytest

import _benchpath  # noqa: F401
from lib import harness


_ids = itertools.count()


def _span(name, dur_us, parent=None, *, sid=None, **args):
    sid = next(_ids) if sid is None else sid
    return {"name": name, "ph": "X", "ts": 0.0, "dur": dur_us,
            "pid": 0, "tid": 1,
            "args": dict(args, id=sid, parent=parent, depth=0)}


def _sweep(scan_us, mask_us, page_us, decode_us, wb_us, *, pages=4,
           buckets=2, flagged=3):
    """One sweep's spans as the program records them (children first,
    each naming the sweep as its parent)."""
    sweep = next(_ids)
    return [
        _span("scrub.scan_dispatch", scan_us, sweep, dispatches=pages),
        _span("scrub.mask_pull", mask_us, sweep, bytes=pages * 256),
        _span("scrub.page_pull", page_us, sweep, bytes=flagged * 2**20),
        _span("repair.decode", decode_us, sweep, dispatches=buckets,
              bytes=buckets * 2**19),
        _span("repair.writeback", wb_us, sweep, bytes=flagged * 2**20),
        _span("scrub.sweep", 10_000_000, sid=sweep),
    ]


def _outside_sweep():
    """A repair drain under a span that is not a sweep (a read-path
    repair): its decode and writeback are not the scrub's."""
    step = next(_ids)
    return [_span("repair.decode", 10**9, step, dispatches=10**6,
                  bytes=2**30),
            _span("repair.writeback", 10**9, step, bytes=2**30),
            _span("engine.step", 3 * 10**9, sid=step)]


SPANS = (_sweep(1000, 2000, 3000, 4000, 5000)
         + _sweep(3000, 4000, 5000, 6000, 7000, buckets=3, flagged=1)
         + _outside_sweep()
         + [_span("bench.other", 999_999, bytes=2**30, dispatches=10**6),
            {"name": "mark", "ph": "i", "ts": 0.0, "args": {}}])

EXPECTED = {
    "sweep_ms.scan_dispatch": 2.0,
    "sweep_ms.mask_pull": 3.0,
    "sweep_ms.page_pull": 4.0,
    "sweep_ms.decode": 5.0,
    "sweep_ms.writeback": 6.0,
    # masks 1 KiB, pages and writebacks 3 + 1 MiB each, buckets 1 + 1.5 MiB
    "sweep_host_mib": (2 * 1024 / 2**20 + 2 * (3 + 1) + 2.5) / 2,
    # 4 scans and 2 or 3 decode buckets a sweep
    "sweep_dispatches": (4 + 2 + 4 + 3) / 2,
}


def _ctx(spans):
    return harness.RunContext(config={}, mix={}, window={"kind": "sweeps"},
                              peaks={}, spans=spans, reduced={})


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_scrub_span_metric_reads_per_sweep(name):
    value = harness.load_module("metrics", name).read(_ctx(SPANS))
    assert value == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_scrub_span_metric_reads_nothing_without_a_sweep(name):
    mod = harness.load_module("metrics", name)
    assert mod.read(_ctx([])) is None
    # a program without the scrub spans (the parent of this metric) still
    # records the harness's own events: the metric stays silent there too
    assert mod.read(_ctx(_outside_sweep())) is None


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_scrub_span_metric_follows_the_parent_chain(name):
    """A phase nested deeper under a sweep still counts; spans without
    `id`/`parent` (a tracer that records no tree) read nothing."""
    mod = harness.load_module("metrics", name)
    sweeps = {e["args"]["id"] for e in SPANS if e["name"] == "scrub.sweep"}
    deeper = []
    for e in SPANS:
        parent = e["args"].get("parent")
        if parent in sweeps:
            mid = _span("drain", 1.0, parent)
            e = dict(e, args=dict(e["args"], parent=mid["args"]["id"]))
            deeper.append(mid)
        deeper.append(e)
    assert mod.read(_ctx(deeper)) == pytest.approx(EXPECTED[name])
    flat = [dict(e, args={k: v for k, v in e["args"].items()
                          if k not in ("id", "parent")}) for e in SPANS]
    assert mod.read(_ctx(flat)) is None
