"""Each kernel's work function at known shapes, against hand counts."""
import math

import pytest

import _benchpath  # noqa: F401
from lib import harness


def test_scan_syndromes_work():
    scan = harness.load_module("kernels", "scan_syndromes")
    # wl1024_r08: n 1024 over GF(3), 3072 nonzeros in H; one 256-word page
    ops, nbytes = scan.work(words=256, n=1024, p=3, nnz_h=3072)
    assert ops == 2 * 3072 * 256
    # ceil(1024 * log2 3) = 1624 bits = 203 bytes, plus a flag byte
    assert math.ceil(1024 * math.log2(3)) == 1624
    assert nbytes == pytest.approx(256 * (203 + 1))


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        harness.load_peaks("TPU v99 imaginary")
    v5e = harness.load_peaks("TPU v5 lite")
    assert v5e["bf16_flops"] == 197e12 and v5e["int8_ops"] == 393e12
    assert v5e["hbm_bytes_per_s"] == 819e9 and "source" in v5e
