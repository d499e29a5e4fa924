"""The benchmark's comparison has to fail a broken timed path.

Each test drives a whole run of a cell through the harness on the CPU at
a small size (the harness's look for a chip skipped) with the timed path
broken underneath, and sees `correct` come out false; the sound run of
the same size comes out true.
"""
import json
import os
import shutil
import time

import pytest

import _benchpath
import calibrate
from lib import harness

CONFIGS = os.path.join(_benchpath.BENCH, "configs")

MEM_MIX = {"kind": "sweeps", "damage": {"share": 0.002,
                                        "errors_per_word": 2}}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A checkout-like directory with a small memory configuration, its
    mix, and the real configuration's generator matrix."""
    r = tmp_path_factory.mktemp("benchroot")
    os.makedirs(r / "cfg")
    os.makedirs(r / "traffic")
    with open(os.path.join(CONFIGS, "nbldpc-mem-wl1024.json")) as f:
        m = json.load(f)
    m["words"] = 16384
    (r / "cfg" / "m.json").write_text(json.dumps(m))
    shutil.copy(os.path.join(CONFIGS, m["generator_file"]), r / "cfg")
    (r / "traffic" / "sweeps.json").write_text(json.dumps(MEM_MIX))
    return str(r)


SPEC = {"configs": [{"name": "m", "file": "cfg/m.json"}],
        "workloads": [{"name": "sweeps", "config": "m", "traffic": "sweeps",
                       "chips": 1}],
        "end_to_end": [{"name": "setup_s", "unit": "s"}],
        "per_layer": []}


def _run(root, cell, factory=None, seconds=1.0):
    cell = harness.find_cell(SPEC, cell)
    return harness.run_cell(
        root, SPEC, cell, seed=2 ** 32 + 3, seconds=seconds, trace=False,
        device={"platform": "cpu", "kind": "cpu", "count": 1},
        t_start=time.perf_counter(), check_kernels=False,
        system_factory=factory, mixes=root)


def _memory(root):
    return harness.load_module("systems", "memory").System


def test_sound_memory_run_is_correct(root):
    res = _run(root, "sweeps")
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0


def test_scrub_that_writes_nothing_back_is_not_correct(root):
    base = _memory(root)

    class NoWriteback(base):
        def run(self, seconds):
            calibrate.drop_writeback(self.store)
            return super().run(seconds)

    res = _run(root, "sweeps", NoWriteback)
    assert not res["correct"]
    assert res["checks"]["words_not_as_written"]["value"] > 0


def test_repaired_word_altered_is_not_correct(root):
    base = _memory(root)

    class Altered(base):
        def run(self, seconds):
            store, set_page = self.store, self.store._set_page

            def altered(i, page):
                set_page(i, page.at[0, 0].set((page[0, 0] + 1) % 3))

            scrub = store.scrub

            def broken(*a, **kw):
                store._set_page = altered
                try:
                    return scrub(*a, **kw)
                finally:
                    del store._set_page

            store.scrub = broken
            return super().run(seconds)

    res = _run(root, "sweeps", Altered)
    assert not res["correct"]


def test_scrub_of_half_the_pages_is_not_correct(root):
    base = _memory(root)

    class HalfSwept(base):
        def run(self, seconds):
            store, scrub = self.store, self.store.scrub
            store.scrub = lambda pages=None, **kw: scrub(
                range(store.n_pages // 2), **kw)
            return super().run(seconds)

    res = _run(root, "sweeps", HalfSwept)
    assert not res["correct"]
    assert res["checks"]["sweep_flag_misses"]["value"] > 0
