"""The serving cell's pieces, each against a hand count: the request
generator, the closed-loop admission count, `tokens_per_s`, the work
functions, the per-step span metrics, the reference's weights and page
quantization against the program's, and the pinned code tables."""
import itertools
import json
import math
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _benchpath
import _serving
from lib import harness
from lib import requests as reqs
from test_bench_code import _rank_mod_p

MIX_FILE = os.path.join(_benchpath.BENCH, "traffic", "batch-longdoc.json")
CONFIG = os.path.join(_serving.CONFIGS, f"{_serving.REAL}.json")


@pytest.fixture(scope="module")
def mix():
    with open(MIX_FILE) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def cfg():
    with open(CONFIG) as f:
        return json.load(f)


# -- the request stream ------------------------------------------------------


@pytest.mark.parametrize("seed", [1, 2 ** 31 + 77, 2 ** 33 + 5])
def test_stream_is_deterministic_and_in_range(mix, seed):
    a = [reqs.request(mix, seed, i, 49155) for i in range(40)]
    b = [reqs.request(mix, seed, i, 49155) for i in range(40)]
    for x, y in zip(a, b):
        assert np.array_equal(x.prompt, y.prompt) and x.max_new == y.max_new
    for r in a:
        assert 128 <= len(r.prompt) <= 384 and 16 <= r.max_new <= 128
        assert r.prompt.dtype == np.int32
        assert r.prompt.min() >= 0 and r.prompt.max() < 49155
    assert reqs.max_context(mix) == 512


def test_every_seed_gets_the_same_sizes_in_another_order(mix):
    n = mix["block"]
    for block in range(3):
        one = [reqs.request(mix, 5, block * n + i, 100) for i in range(n)]
        two = [reqs.request(mix, 2 ** 33 + 9, block * n + i, 100)
               for i in range(n)]
        key = lambda r: (len(r.prompt), r.max_new)  # noqa: E731
        assert sorted(map(key, one)) == sorted(map(key, two))
        assert [key(r) for r in one] != [key(r) for r in two]
        assert not np.array_equal(one[0].prompt[:16], two[0].prompt[:16])


def test_distributions_have_their_stated_shape(mix):
    r = np.random.default_rng(0)
    p = reqs.draw(mix["prompt"], r, 20000)
    o = reqs.draw(mix["output"], r, 20000)
    assert abs(np.median(p) - 224) <= 3 and p.min() == 128 and p.max() == 384
    assert abs(np.median(o) - 32) <= 1 and o.min() == 16 and o.max() == 128
    # Pareto with scale 16 and median 32 has tail index 1: P(X > x) = 16/x
    assert np.mean(o >= 64) == pytest.approx(0.25, abs=0.015)
    assert np.mean(o == 128) == pytest.approx(0.125, abs=0.01)


def test_admissions_of_a_closed_loop():
    sizes = {2: (20, 3), 3: (40, 3), 4: (5, 3)}.get
    # slot 0: 1 token of 3 so far (2 steps left); slot 1: 1 of 5 (4 left)
    idx = reqs.admissions([(30, 1, 3), (10, 1, 5)], first=2, steps=6,
                          sizes=sizes)
    # step 3 admits 2 into slot 0; step 5 admits 3 (slot 0) and 4 (slot 1)
    assert idx == [2, 3, 4]
    assert reqs.admissions([None, None], first=7, steps=1,
                           sizes=lambda i: (16, 16)) == [7, 8]
    assert reqs.admissions([(9, 1, 16)], first=1, steps=14,
                           sizes=lambda i: (16, 16)) == []


# -- tokens_per_s ------------------------------------------------------------


def _window(tokens_per_step, ends, t0=0.0):
    steps = [{"tokens": n, "admitted": 0} for n in tokens_per_step]
    return {"kind": "serving", "t0": t0, "t1": ends[-1],
            "tokens": sum(tokens_per_step), "steps": steps}


def test_tokens_per_s_is_over_the_whole_window():
    rate = harness.load_module("e2e", "tokens_per_s")
    steady = _window([8] * 10, [1.0 * i for i in range(1, 11)])
    stalled = _window([8] * 10, [1.0 * i for i in range(1, 10)] + [15.0])
    assert rate.compute(steady) == pytest.approx(80 / 10.0)
    assert rate.compute(stalled) == pytest.approx(80 / 15.0)
    assert rate.compute(_window([4, 4], [3.0, 4.0], t0=2.0)) == \
        pytest.approx(4.0)
    assert rate.compute({"kind": "sweeps", "t0": 0, "t1": 1}) is None


# -- work functions ----------------------------------------------------------


def test_attend_protected_work():
    k = harness.load_module("kernels", "attend_protected")
    # wl160_r08 words: ceil(160 log2 3) = 254 bits = 31.75 bytes; 384
    # words a (16, 8, 64) int8 page
    ops, nbytes = k.work(pages=10, hot_tokens=5, queries=2,
                         words_per_page=384, n=160, p=3, page_tokens=16,
                         hq=32, hkv=8, dh=64)
    assert math.ceil(160 * math.log2(3)) == 254
    assert ops == 4 * 32 * 64 * (10 * 16 + 5)
    assert nbytes == pytest.approx(10 * (2 * 384 * 31.75 + 8)
                                   + 5 * 2 * 8 * 64 * 2 + 2 * 2 * 32 * 64 * 2)


def test_serve_step_flops_of_granite(cfg):
    k = harness.load_module("kernels", "serve_step")
    shape = dict(d=2048, ff=8192, layers=40, hq=32, hkv=8, dh=64,
                 vocab=49155)
    per_block = 2048 * 2048 * 2 + 2048 * 512 * 2 + 3 * 2048 * 8192
    assert per_block == 60_817_408
    w = 40 * per_block
    head = 2 * 2048 * 49155
    assert k.work(contexts=[300], prompts=[], **shape) == \
        2 * w + head + 4 * 32 * 64 * 40 * 300
    assert k.work(contexts=[], prompts=[3], **shape) == \
        2 * w * 3 + 4 * 32 * 64 * 40 * 6 + head
    # about 5.07 GFLOP a decoded token: the 2.53 B weights twice
    assert k.work(contexts=[0], prompts=[], **shape) == \
        pytest.approx(5.07e9, rel=2e-3)


# -- per-step span metrics ---------------------------------------------------

_ids = itertools.count()


def _span(name, dur_us, parent=None, *, sid=None):
    sid = next(_ids) if sid is None else sid
    return {"name": name, "ph": "X", "ts": 0.0, "dur": dur_us, "pid": 0,
            "tid": 1, "args": {"id": sid, "parent": parent, "depth": 0}}


def _step(decode_us, sync_us, admit_us=None):
    step = next(_ids)
    admit = next(_ids)
    out = [_span("engine.decode", decode_us, step),
           _span("engine.sample_sync", sync_us, step),
           _span("engine.admit", admit_us or 10.0, step, sid=admit)]
    if admit_us:
        out.append(_span("engine.prefill", admit_us - 5, admit))
    return out + [_span("engine.step", 10_000_000, sid=step)]


def _ctx(spans):
    return types.SimpleNamespace(spans=spans, window={"kind": "serving"})


def test_step_ms_metrics_per_step():
    spans = _step(4000.0, 200.0) + _step(6000.0, 400.0, admit_us=9000.0)
    # a decode outside any engine.step is not a step's
    spans.append(_span("engine.decode", 99_000.0))
    ctx = _ctx(spans)
    read = lambda m: harness.load_module("metrics", m).read(ctx)  # noqa
    assert read("step_ms.decode") == pytest.approx(5.0)
    assert read("step_ms.sample_sync") == pytest.approx(0.3)


def test_step_ms_reads_nothing_without_steps_or_ids():
    read = lambda m, c: harness.load_module("metrics", m).read(c)  # noqa
    assert read("step_ms.decode", _ctx([])) is None
    bare = [{"name": "engine.step", "ph": "X", "dur": 5.0, "args": {}}]
    assert read("step_ms.decode", _ctx(bare)) is None
    sweeps = types.SimpleNamespace(spans=_step(1.0, 1.0),
                                   window={"kind": "sweeps"})
    assert read("step_ms.decode", sweeps) is None


def test_span_tree_reaches_roots_through_nested_parents():
    from lib.span_tree import under
    step = _span("engine.step", 1.0)
    admit = _span("engine.admit", 1.0, step["args"]["id"])
    prefill = _span("engine.prefill", 1.0, admit["args"]["id"])
    stray = _span("engine.prefill", 1.0, 10 ** 9)
    roots, spans = under(_ctx([step, admit, prefill, stray]), "engine.step")
    assert roots == 1
    assert sorted(e["name"] for e in spans) == ["engine.admit",
                                               "engine.prefill"]
    assert under(_ctx([admit, prefill]), "engine.step") == (0, [])


def test_serve_metrics_read_nothing_on_an_empty_window():
    ctx = types.SimpleNamespace(
        window={"kind": "serving", "t0": 0.0, "t1": 1.0, "work": {
            "serve_step": {"contexts": [], "prompts": []},
            "attend_protected": {"queries": 0}}},
        reduced={"op_time": {}, "busy_s": 0.5, "window_s": 1.0},
        peaks={"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9})
    read = lambda m: harness.load_module("metrics", m).read(ctx)  # noqa
    assert read("serve_mfu") is None
    assert read("attend_protected_roofline") is None
    assert read("idle_share.serve") == pytest.approx(50.0)


# -- the reference against the program's pieces ------------------------------


@pytest.fixture(scope="module")
def ref():
    cfg = {"dir": _serving.CONFIGS, "reference": f"{_serving.REAL}.ref"}
    return harness.load_module("systems", "serving").load_reference(cfg)


def test_stacked_weights_are_the_layer_weights(ref, cfg):
    tiny = dict(cfg, **dict(_serving.TINY, num_hidden_layers=3))
    keys = ref.weight_keys(2 ** 33 + 3)
    stacked = ref.stacked_weights(tiny, keys)
    for layer in range(3):
        one = ref.layer_weights(tiny, keys, layer)
        for name, w in one.items():
            assert w.dtype == jnp.bfloat16
            assert np.array_equal(np.asarray(stacked[name][layer]),
                                  np.asarray(w)), (name, layer)
    source = dict(cfg, **dict(_serving.TINY, initializer_range=0.02))
    w = np.asarray(ref.layer_weights(source, ref.weight_keys(7), 0)[
        "w_gate"], np.float32)
    assert w.std() == pytest.approx(0.02, rel=0.05)
    assert len(np.unique(w)) <= 255


def test_page_quantization_matches_the_program(ref):
    from repro.memory.paged import dequantize_tensor, quantize_tensor
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 32, 8, 64),
                          jnp.float32) * 0.7
    mine = ref.page_quantize(x, "int8", 16)
    for b in range(2):
        for j in range(2):
            page = x[b:b + 1, 16 * j:16 * (j + 1)]
            words, meta = quantize_tensor(page, 3, 128)
            theirs = dequantize_tensor(words, meta, 3)
            assert np.array_equal(np.asarray(theirs),
                                  np.asarray(mine[b:b + 1,
                                                  16 * j:16 * (j + 1)]))


def test_reference_prompt_logits_match_the_program_prefill(ref, cfg):
    """Prompt positions (dense attention) of the float32 reference against
    the program's bfloat16 prefill on the same weights."""
    import dataclasses

    from repro.configs import get_config
    from repro.models import lm
    tiny = dict(cfg, **_serving.TINY)
    seed = 2 ** 32 + 1
    keys = ref.weight_keys(seed)
    w = ref.stacked_weights(tiny, keys)
    L, d = tiny["num_hidden_layers"], tiny["hidden_size"]
    ones = jnp.ones((L, d), jnp.float32)
    params = {"embed": ref.embedding(tiny, keys),
              "final_norm": {"scale": jnp.ones((d,), jnp.float32)},
              "groups": {"pos0": {
                  "norm1": {"scale": ones}, "norm2": {"scale": ones},
                  "attn": {k: w[k] for k in ("wq", "wk", "wv", "wo")},
                  "mlp": {k: w[k] for k in ("w_gate", "w_up", "w_down")}}}}
    arch = dataclasses.replace(
        get_config("granite_3_2b"), d_model=64, n_groups=2, n_heads=4,
        n_kv_heads=2, head_dim=16, d_ff=128, vocab_size=512)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 48), 0, 512)
    theirs, _ = lm.prefill(params, arch, tokens)
    plen = jnp.full((2,), 48, jnp.int32)
    out = {}
    for dtype in (jnp.float32, jnp.bfloat16):
        x, emb = ref.final_hidden(tiny, seed, tokens, plen, page=16,
                                  dtype=dtype)
        out[dtype] = ref.head_logits(x, emb, eps=tiny["rms_norm_eps"],
                                     dtype=jnp.float32)
    mine = out[jnp.float32]
    # the program (bfloat16 throughout) lies about as far from the float32
    # reference as the reference's own bfloat16 matmuls do (0.28 against
    # 0.17 at a logit spread of 2.0); a wrong equation would be off by
    # the spread itself
    err = float(jnp.max(jnp.abs(theirs - mine)))
    own = float(jnp.max(jnp.abs(out[jnp.bfloat16] - mine)))
    assert err < 2.5 * own, (err, own)
    assert own < 0.15 * float(jnp.std(mine))
    agree = jnp.mean(jnp.argmax(theirs, -1) == jnp.argmax(mine, -1))
    assert float(agree) >= 0.9


# -- the pinned code ----------------------------------------------------------


def test_kv_code_tables_form_the_programs_code(cfg):
    from repro.core import get_code
    srv = cfg["serving"]
    mod = harness.load_module("systems", "serving")
    cfg = dict(cfg, dir=_serving.CONFIGS)
    P = mod.load_table(cfg, "generator_file").astype(np.int64)
    H = mod.load_table(cfg, "parity_check_file").astype(np.int64)
    n, k, p = srv["n"], srv["k"], srv["p"]
    assert P.shape == (k, n - k) and H.shape == (n - k, n)
    G = np.concatenate([np.eye(k, dtype=np.int64), P], axis=1)
    assert not (H @ G.T % p).any()
    assert _rank_mod_p(H, p) == n - k
    code = get_code(srv["code"])
    assert np.array_equal(np.asarray(code.P) % p, P)
    assert np.array_equal(np.asarray(code.H) % p, H)
