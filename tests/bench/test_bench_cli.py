"""The benchmark's command refuses to measure without a TPU."""
import os
import subprocess
import sys

import _benchpath


def test_no_tpu_no_result():
    root = os.path.dirname(_benchpath.BENCH)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(_benchpath.BENCH, "run.py"),
         "--workload", "mem.scrub-damaged", "--seed", str(2 ** 33 + 1),
         "--seconds", "1", "--trace", "0"],
        cwd=root, env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr
