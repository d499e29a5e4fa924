"""The beyond-paper performance paths: shard_map expert-parallel MoE
(subprocess with 8 placeholder devices) and budgeted detect-then-correct."""
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (PIMConfig, ProtectionConfig, encode_weight_matrix,
                        get_code)
from repro.core.protected import (protected_pim_matmul,
                                  protected_pim_matmul_budgeted)


def test_budgeted_correction_matches_full(rng):
    code = get_code("wl160_r08")
    W = jnp.asarray(rng.integers(-1, 2, (32, 2 * code.k)), jnp.int32)
    We = encode_weight_matrix(W, code)
    x = jnp.asarray(rng.integers(-1, 2, (8, 32)), jnp.int32)
    exact = np.asarray(x @ W)
    cfgp = PIMConfig(output_error_rate=0.003)
    key = jax.random.PRNGKey(3)
    prot = ProtectionConfig(mode="correct", n_iters=10, damping=0.3)
    full = protected_pim_matmul(x, We, code, prot, cfgp, key=key)
    budg = protected_pim_matmul_budgeted(x, We, code, prot, cfgp, key=key,
                                         budget=16)
    raw = protected_pim_matmul(x, We, code, ProtectionConfig(mode="off"),
                               cfgp, key=key)
    ef = (np.asarray(full.y) != exact).mean()
    eb = (np.asarray(budg.y) != exact).mean()
    er = (np.asarray(raw.y) != exact).mean()
    assert er > 0
    assert eb <= ef + 1e-9
    assert eb < er / 2


def test_budgeted_overflow_flagged(rng):
    """More flagged words than budget -> uncorrected flags raised."""
    code = get_code("wl40_r08")
    W = jnp.asarray(rng.integers(-1, 2, (16, 8 * code.k)), jnp.int32)
    We = encode_weight_matrix(W, code)
    x = jnp.asarray(rng.integers(-1, 2, (8, 16)), jnp.int32)
    cfgp = PIMConfig(output_error_rate=0.08)       # floods the budget
    prot = ProtectionConfig(mode="correct", n_iters=4)
    res = protected_pim_matmul_budgeted(x, We, code, prot, cfgp,
                                        key=jax.random.PRNGKey(0), budget=2)
    assert bool(np.asarray(res.detected).any())
    assert bool(np.asarray(res.uncorrected).any())


def _budgeted_setup(rng, err_rate):
    code = get_code("wl40_r08")
    W = jnp.asarray(rng.integers(-1, 2, (16, 8 * code.k)), jnp.int32)
    We = encode_weight_matrix(W, code)
    x = jnp.asarray(rng.integers(-1, 2, (8, 16)), jnp.int32)
    prot = ProtectionConfig(mode="correct", n_iters=8, damping=0.3)
    cfgp = PIMConfig(output_error_rate=err_rate)
    return code, We, x, prot, cfgp


def _unambiguous_column(code) -> int:
    """First column whose single-error syndromes e·H[:, j] (e != 0) no other
    (column, value) pair shares: one error there is always correctable."""
    H = np.asarray(code.H) % code.p
    seen: dict = {}
    for j in range(code.n):
        for e in range(1, code.p):
            seen.setdefault(tuple((e * H[:, j]) % code.p), []).append(j)
    return next(j for j in range(code.n)
                if all(len(seen[tuple((e * H[:, j]) % code.p)]) == 1
                       for e in range(1, code.p)))


def test_budgeted_overflow_spares_corrected_words(rng):
    """Regression: on budget overflow, words the budget DID correct must not
    be reported uncorrected — only decode failures and the flagged words the
    budget never reached."""
    code, We, x, prot, cfgp = _budgeted_setup(rng, 0.0)
    # placed faults: one weight cell off by one in each codeword block puts
    # exactly one (correctable) error into every word of each batch row
    # that reads that weight row — an overflow the budget can correct
    i = int(np.argmax((np.asarray(x) != 0).sum(axis=0)))
    j = _unambiguous_column(code)
    We = We.at[i, j::code.n].add(1)
    res = protected_pim_matmul_budgeted(x, We, code, prot, cfgp,
                                        key=jax.random.PRNGKey(0), budget=2)
    det = np.asarray(res.detected)
    unc = np.asarray(res.uncorrected)
    assert det.sum() > 2                           # genuine overflow
    assert not (unc & ~det).any()                  # uncorrected ⊆ detected
    # at most `budget` words left the uncorrected set...
    assert unc.sum() >= det.sum() - 2
    # ...and at least one selected word was corrected and NOT blamed for
    # the overflow (the old accounting marked every detected word)
    assert unc.sum() < det.sum()


def test_budgeted_reports_per_word_decode_failures(rng):
    """Regression: per-word decoder failures within the budget were silently
    dropped. With a budget covering every flagged word, the budgeted path's
    uncorrected mask must equal the full path's detect_fail exactly."""
    for err_rate in (0.003, 0.25):                 # sparse and flooded
        code, We, x, prot, cfgp = _budgeted_setup(rng, err_rate)
        key = jax.random.PRNGKey(0)
        budg = protected_pim_matmul_budgeted(x, We, code, prot, cfgp,
                                             key=key, budget=64)
        full = protected_pim_matmul(x, We, code, prot, cfgp, key=key)
        np.testing.assert_array_equal(np.asarray(budg.detected),
                                      np.asarray(full.detected))
        np.testing.assert_array_equal(np.asarray(budg.uncorrected),
                                      np.asarray(full.uncorrected))
    # the flooded regime must actually contain decoder failures, or the
    # equality above proves nothing about failure accounting
    assert np.asarray(full.uncorrected).sum() > 0


_SHARD_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys; sys.path.insert(0, "src")
import dataclasses
import numpy as np, jax, jax.numpy as jnp
from repro.configs import get_config
from repro.nn.moe import init_moe, moe_dense
from repro.nn.moe_shard import moe_shard_apply
from repro.distributed.sharding import use_rules

cfg = get_config("olmoe_1b_7b").reduced(n_experts=8)
cfg = dataclasses.replace(cfg, top_k=2, capacity_factor=8.0)
from jax.sharding import AxisType
mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(AxisType.Auto,) * 2)
key = jax.random.PRNGKey(0)
params = init_moe(key, cfg, 32)
rng = np.random.default_rng(0)
x = jnp.asarray(rng.normal(size=(2, 16, cfg.d_model)).astype(np.float32)
                ).astype(jnp.bfloat16)
y_ref = moe_dense(params, x.reshape(-1, cfg.d_model), cfg).reshape(x.shape)
with use_rules(mesh, {"batch": "data", "expert": "model"}):
    with mesh:
        y = jax.jit(lambda p, x: moe_shard_apply(p, x, cfg))(params, x)
err = float(jnp.max(jnp.abs(y.astype(jnp.float32) - y_ref.astype(jnp.float32))))
assert err < 0.1, err
def loss(p, x):
    return (moe_shard_apply(p, x, cfg)**2).sum().astype(jnp.float32)
with use_rules(mesh, {"batch": "data", "expert": "model"}):
    with mesh:
        g = jax.jit(jax.grad(loss))(params, x)
assert all(bool(jnp.isfinite(t).all()) for t in jax.tree.leaves(g))
print("SHARD_EP_OK", err)
"""


def test_shard_ep_moe_multidevice():
    """shard_map MoE vs dense oracle on 8 placeholder devices (subprocess so
    the main test process keeps its single real device)."""
    r = subprocess.run([sys.executable, "-c", _SHARD_SCRIPT],
                       capture_output=True, text=True, timeout=420)
    assert "SHARD_EP_OK" in r.stdout, r.stdout + r.stderr


def test_shard_ep_falls_back_without_mesh(rng):
    import dataclasses
    from repro.configs import get_config
    from repro.nn.moe import init_moe
    from repro.nn.moe_shard import moe_shard_apply
    cfg = get_config("olmoe_1b_7b").reduced(n_experts=8)
    cfg = dataclasses.replace(cfg, capacity_factor=8.0)
    params = init_moe(jax.random.PRNGKey(0), cfg, 32)
    x = jnp.asarray(rng.normal(size=(2, 8, cfg.d_model)).astype(np.float32))
    y = moe_shard_apply(params, x.astype(jnp.bfloat16), cfg)
    assert y.shape == x.shape and bool(jnp.isfinite(
        y.astype(jnp.float32)).all())
