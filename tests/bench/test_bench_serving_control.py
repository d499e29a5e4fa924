"""The serving cell's control at a tiny size on the CPU: the reference put
in the program's place, at the configuration's bfloat16 matmuls and with
its KV pages in int4, the precision below the configuration's int8, read
through the same `verify` as the program on the window's own requests,
fails every limit that sound runs of the same size pass
(`bench/calibrate_serving.py` reads the same at the cell's size on the
chip)."""
import gc

import pytest

import _serving


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return _serving.make_root(tmp_path_factory.mktemp("controlroot"))


@pytest.fixture(scope="module")
def serving():
    return _serving.serving_module()


@pytest.mark.parametrize("seed", [5, 6, 2 ** 32 + 11])
def test_controls_fail_the_limit_that_sound_runs_pass(root, serving, seed):
    s = _serving.system(root, serving, seed)
    checks = {c["name"]: c for c in s.verify()}
    assert all(c["ok"] for c in checks.values()), checks
    control = {c["name"]: c for c in s.verify(control="int4")}
    assert not any(control[k]["ok"] for k in _serving.TINY_LIMITS), control
    del s
    gc.collect()
