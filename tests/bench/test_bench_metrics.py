"""Metric arithmetic: rates over the whole window, and which cells report
which metric."""
import pytest

import _benchpath  # noqa: F401
from lib import harness


def _sweeps_window(sweep_ends, words_per_sweep=800, t0=0.0):
    """Whole sweeps of `words_per_sweep` words, each ending at its time."""
    return {"kind": "sweeps", "t0": t0, "t1": sweep_ends[-1],
            "words_swept": words_per_sweep * len(sweep_ends)}


def test_rate_is_over_the_whole_window_and_drops_with_a_stall():
    rate = harness.load_module("e2e", "scrub_words_per_s")
    steady = _sweeps_window([0.5 * i for i in range(1, 21)])
    stalled = _sweeps_window([0.5 * i for i in range(1, 20)] + [15.0])
    assert rate.compute(steady) == pytest.approx(20 * 800 / 10.0)
    assert rate.compute(stalled) == pytest.approx(20 * 800 / 15.0)
    assert rate.compute(_sweeps_window([4.0], t0=2.0)) == pytest.approx(400)
    assert rate.compute({"kind": "serving", "t0": 0, "t1": 1}) is None


def test_which_cells_report_which_metric():
    spec = {"end_to_end": [
        {"name": "a_per_s", "workloads": ["x"]},
        {"name": "setup_s"}],
        "per_layer": [
            {"name": "k_roofline", "moves": "a_per_s", "workloads": ["x"]},
            {"name": "idle", "moves": "a_per_s"},
            {"name": "other", "moves": "b_per_s"}]}
    assert [m["name"] for m in harness.cell_metrics(
        spec, "x", per_layer=False)] == ["a_per_s", "setup_s"]
    assert [m["name"] for m in harness.cell_metrics(
        spec, "x", per_layer=True)] == ["k_roofline", "idle"]
    assert [m["name"] for m in harness.cell_metrics(
        spec, "y", per_layer=True)] == []


def test_every_metric_and_mix_in_the_benchmark_has_its_file():
    import json
    import os
    root = os.path.dirname(_benchpath.BENCH)
    spec = harness.load_benchmark(root)
    for m in spec["end_to_end"]:
        if m["name"] != "setup_s":
            assert hasattr(harness.load_module("e2e", m["name"]), "compute")
    for m in spec["per_layer"]:
        assert hasattr(harness.load_module("metrics", m["name"]), "read")
    for cell in spec["workloads"]:
        cfg = harness.load_config(root, spec, cell["config"])
        assert hasattr(harness.load_module("systems", cfg["system"]),
                       "System")
        with open(os.path.join(_benchpath.BENCH, "traffic",
                               f"{cell['traffic']}.json")) as f:
            json.load(f)
