"""Data pipeline, optimizers, checkpointing, fault tolerance, compression,
sharding rules, baselines."""
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hyp import given, settings, st

from repro import checkpoint as ckpt
from repro.core.baselines import HammingSECDED, ModuloParity, SuccessiveCorrection
from repro.data import DataConfig, TokenPipeline
from repro.distributed.compression import dequantize, init_ef, quantize_ef
from repro.distributed.fault import RestartManager, StragglerWatchdog
from repro.distributed.sharding import resolve_spec, use_rules
from repro.optim import adafactor, adamw, clip_grads, warmup_cosine


# ---------------------------------------------------------------------------
# data
# ---------------------------------------------------------------------------

def test_data_determinism_and_resume():
    c = DataConfig(vocab_size=100, seq_len=16, global_batch=4)
    p = TokenPipeline(c)
    batches = [next(p) for _ in range(3)]
    q = TokenPipeline.restore(c, {"step": 1, "seed": 0})
    assert np.array_equal(next(q)["tokens"], batches[1]["tokens"])
    # labels are next-token shifted
    b = batches[0]
    assert np.array_equal(b["tokens"][:, 1:], b["labels"][:, :-1])


def test_data_shards_disjoint_and_elastic():
    c = DataConfig(vocab_size=100, seq_len=8, global_batch=8)
    a0 = next(TokenPipeline(c, 0, 2))["tokens"]
    a1 = next(TokenPipeline(c, 1, 2))["tokens"]
    assert not np.array_equal(a0, a1)
    # elastic: resharding to 4 shards still yields deterministic streams
    b0 = next(TokenPipeline(c, 0, 4))["tokens"]
    assert b0.shape == (2, 8)


def test_data_has_learnable_structure():
    c = DataConfig(vocab_size=64, seq_len=256, global_batch=2)
    toks = next(TokenPipeline(c))["tokens"]
    # Markov structure: bigram entropy < unigram entropy by a margin
    flat = toks.reshape(-1)
    uni = np.bincount(flat, minlength=64) / flat.size
    h_uni = -(uni[uni > 0] * np.log(uni[uni > 0])).sum()
    pairs = flat[:-1] * 64 + flat[1:]
    joint = np.bincount(pairs, minlength=64 * 64) / pairs.size
    h_joint = -(joint[joint > 0] * np.log(joint[joint > 0])).sum()
    h_cond = h_joint - h_uni
    assert h_cond < h_uni - 0.3


# ---------------------------------------------------------------------------
# optimizers
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("make,thresh", [(lambda: adamw(5e-2), 0.05),
                                         (lambda: adafactor(5e-1), 0.05),
                                         (lambda: adafactor(3e-1, momentum=0.5),
                                          0.25)])
def test_optimizers_converge_quadratic(make, thresh):
    tx = make()
    params = {"w": jnp.ones((6, 3)), "b": jnp.zeros((3,))}
    target = jnp.asarray([1.0, -2.0, 0.5])

    def loss(p):
        return jnp.sum((jnp.ones((6,)) @ p["w"] + p["b"] - target) ** 2)

    state = tx.init(params)
    l0 = float(loss(params))
    for _ in range(150):
        g = jax.grad(loss)(params)
        params, state, _ = tx.update(g, state, params)
    assert float(loss(params)) < thresh * l0


def test_grad_clipping():
    g = {"a": jnp.full((4,), 100.0)}
    clipped, gn = clip_grads(g, 1.0)
    assert float(gn) == pytest.approx(200.0)
    norm = float(jnp.sqrt(jnp.sum(clipped["a"] ** 2)))
    assert norm == pytest.approx(1.0, rel=1e-5)


def test_warmup_cosine_schedule():
    s = warmup_cosine(1.0, 10, 100)
    assert float(s(0)) == 0.0
    assert float(s(10)) == pytest.approx(1.0)
    assert float(s(100)) == pytest.approx(0.1, abs=1e-6)
    assert float(s(55)) < float(s(20))


def test_adafactor_memory_is_factored():
    tx = adafactor(1e-2)
    params = {"w": jnp.zeros((64, 32))}
    state = tx.init(params)
    n_state = sum(x.size for x in jax.tree.leaves(state["f"]))
    assert n_state == 64 + 32            # vs 2*64*32 for adamw


# ---------------------------------------------------------------------------
# checkpoint + fault tolerance
# ---------------------------------------------------------------------------

def test_checkpoint_roundtrip_atomic_retention():
    with tempfile.TemporaryDirectory() as d:
        tree = {"p": np.arange(6, np.float32).reshape(2, 3) if False else
                np.arange(6, dtype=np.float32).reshape(2, 3),
                "n": {"s": np.int32(3) * np.ones(2, np.int32)}}
        for step in (10, 20, 30, 40):
            ckpt.save_checkpoint(d, step, tree, keep=2)
        names = sorted(os.listdir(d))
        assert names == ["step_00000030", "step_00000040"]
        out, man = ckpt.restore_checkpoint(d, tree)
        assert man["step"] == 40
        assert np.array_equal(out["p"], tree["p"])


def test_checkpoint_nb_ldpc_protection_corrects_bitflips():
    """The paper's memory mode protecting the framework's own storage.
    Storage rot is injected through the channel API (format-agnostic)."""
    from repro.memory import uniform_flip
    with tempfile.TemporaryDirectory() as d:
        tree = {"w": np.linspace(-1, 1, 32, dtype=np.float32)}
        ckpt.save_checkpoint(d, 1, tree, protect=True)
        n = ckpt.inject_storage_faults(d, uniform_flip(3, 8e-3), key=0)
        assert n > 0                                 # fixed key: deterministic
        out, _ = ckpt.restore_checkpoint(d, tree)
        assert np.array_equal(out["w"], tree["w"])   # ECC fixed the flips


def test_restart_manager_recovers_from_crash():
    with tempfile.TemporaryDirectory() as d:
        mgr = RestartManager(d, save_every=1, max_restarts=2)
        calls = {"n": 0}

        def init_fn():
            return {"x": np.zeros(3, np.float32)}

        def loop(start, data_state):
            calls["n"] += 1
            state = {"x": np.full(3, start, np.float32)}
            for step in range(start, 5):
                state = {"x": state["x"] + 1}
                mgr.maybe_save(step, state, data_state={"step": step,
                                                        "seed": 0})
                if calls["n"] == 1 and step == 3:
                    raise RuntimeError("simulated node failure")
            return 5

        assert mgr.run(loop, init_fn) == 5
        assert calls["n"] == 2
        assert ckpt.latest_step(d) == 4


def test_straggler_watchdog_flags():
    import time
    dog = StragglerWatchdog(threshold=1.5)
    for i in range(3):
        dog.step_start(); time.sleep(0.01); dog.step_end(i)
    dog.step_start(); time.sleep(0.08); dog.step_end(3)
    assert len(dog.flagged) == 1 and dog.flagged[0][0] == 3


# ---------------------------------------------------------------------------
# compression
# ---------------------------------------------------------------------------

@given(st.integers(0, 100))
@settings(max_examples=15, deadline=None)
def test_ef_quantization_error_is_fed_back(seed):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(64,)).astype(np.float32))
    ef = init_ef({"x": x})["x"]
    # repeated quantization of the SAME tensor: error feedback makes the
    # time-average converge to the true value
    acc = np.zeros(64)
    n = 40
    for _ in range(n):
        q, s, ef = quantize_ef(x, ef)
        acc += np.asarray(dequantize(q, s))
    np.testing.assert_allclose(acc / n, np.asarray(x), atol=2e-2)


# ---------------------------------------------------------------------------
# sharding rules
# ---------------------------------------------------------------------------

def test_constrain_is_noop_without_mesh():
    from repro.distributed.sharding import constrain
    x = jnp.ones((4, 4))
    assert constrain(x, "batch", None) is x


def test_resolve_spec_with_rules():
    from jax.sharding import PartitionSpec as P
    from jax.sharding import AxisType
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    with use_rules(mesh, {"batch": "data", "d_ff": "model", "kv_seq": None}):
        assert resolve_spec(("batch", None, "d_ff")) == P("data", None, "model")
        assert resolve_spec(("kv_seq",)) == P(None)


# ---------------------------------------------------------------------------
# baseline ECCs (paper Table 2 comparators)
# ---------------------------------------------------------------------------

def test_hamming_secded_corrects_single_detects_double(rng):
    h = HammingSECDED()
    bits = rng.integers(0, 2, (20, 32))
    word = h.encode(bits)
    # single-bit error in every word -> corrected
    w1 = word.copy()
    for i in range(20):
        w1[i, rng.integers(0, w1.shape[1] - 1)] ^= 1
    dec, unc = h.decode(w1)
    assert (dec == bits).all() and not unc.any()
    # double-bit error -> flagged uncorrectable
    w2 = word.copy()
    w2[:, 3] ^= 1
    w2[:, 9] ^= 1
    _, unc2 = h.decode(w2)
    assert unc2.all()


def test_modulo_parity_detects(rng):
    mp = ModuloParity(q=3)
    W = jnp.asarray(rng.integers(-1, 2, (16, 8)), jnp.int32)
    We = mp.encode_weights(W)
    x = jnp.asarray(rng.integers(-1, 2, (4, 16)), jnp.int32)
    Y = (x @ We).astype(jnp.int32)
    assert not np.asarray(mp.detect(Y)).any()
    Yb = Y.at[1, 2].add(1)
    assert np.asarray(mp.detect(Yb)).any()


def test_successive_correction_fixes_up_to_budget(rng):
    sc = SuccessiveCorrection(max_rereads=3)
    W = jnp.asarray(rng.integers(-1, 2, (16, 10)), jnp.int32)
    x = jnp.asarray(rng.integers(-1, 2, (4, 16)), jnp.int32)
    Y = (x @ W).astype(jnp.int32)
    Yb = Y.at[0, 1].add(1).at[2, 5].add(-1)
    Yf, n = sc.correct(x, W, Yb)
    assert (np.asarray(Yf) == np.asarray(Y)).all()
    assert int(n) == 2
