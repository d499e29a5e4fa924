"""The traffic generator: deterministic under the seed, and every seed
does the same work, placed differently."""
import numpy as np
import pytest

import _benchpath  # noqa: F401
from lib import traffic

MIX = {"damage": {"share": 0.01, "errors_per_word": 2}}


def test_damage_plan():
    w1, c1, d1 = traffic.damage_plan(MIX, 9, 0, 4096, 160, 3)
    w2, c2, d2 = traffic.damage_plan(MIX, 9, 0, 4096, 160, 3)
    assert np.array_equal(w1, w2) and np.array_equal(c1, c2)
    assert np.array_equal(d1, d2)
    assert len(w1) == 41 and len(set(w1.tolist())) == 41
    assert all(len(set(row)) == 2 for row in c1.tolist())
    assert ((d1 >= 1) & (d1 <= 2)).all()
    w3, _, _ = traffic.damage_plan(MIX, 9, 1, 4096, 160, 3)
    assert not np.array_equal(w1, w3)


@pytest.mark.parametrize("seed", [1, 2 ** 33 + 5, 2 ** 31 + 77])
def test_every_seed_does_the_same_work(seed):
    """As many words hit, each with as many wrong symbols, wherever they
    fall."""
    w0, c0, _ = traffic.damage_plan(MIX, 9, 0, 4096, 160, 3)
    w, c, d = traffic.damage_plan(MIX, seed, 0, 4096, 160, 3)
    assert w.shape == w0.shape and c.shape == c0.shape == d.shape
    assert (d != 0).all()
    assert not np.array_equal(w, w0)


def test_jax_keys_for_large_seeds():
    import jax
    a = traffic.jax_key(2 ** 33 + 1, 4)
    b = traffic.jax_key(2 ** 33 + 1, 4)
    c = traffic.jax_key(1, 4)
    assert (jax.random.key_data(a) == jax.random.key_data(b)).all()
    assert not (jax.random.key_data(a) == jax.random.key_data(c)).all()
