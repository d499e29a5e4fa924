"""Fused GF-page paged attention: parity, policy dispatch, deprecations.

The central contract of the fused serving hot path
(`repro.kernels.ops.attend_protected`): attending directly over corrected
GF codeword pages must be BIT-IDENTICAL to the unfused streaming path
(`repro.nn.layers._attend_paged` over decoded/dequantized pages) — for
every registry code, on clean pages, on corrupted-then-corrected pages,
and at quantization edges. The Pallas kernel (interpret mode) keeps fp32
in VMEM (no bf16 round-trip between dequant and QK^T), so it is asserted
allclose at bf16 tolerance against the same reference.

Also covers the `KernelPolicy` redesign: `use_policy` overrides select the
right executable (no stale jit-cache hits), the removed legacy `backend=` /
`scan_backend=` kwargs fail loudly, and the `{"paged": ...}` dict-cache
form still warns through its deprecation window.
"""
from __future__ import annotations

import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import get_code
from repro.core.codes import REGISTRY
from repro.kernels import KernelPolicy, current_policy, ops, use_policy
from repro.memory import asymmetric_adjacent
from repro.models.kv import ProtectedKVConfig, ProtectedKVLayer
from repro.nn.kv_source import KVSource
from repro.nn.layers import _attend_paged


@pytest.fixture
def rng():
    return np.random.default_rng(0)


def _mk_layer(code_name: str, *, batch=2, hkv=2, dh=8, page_tokens=4,
              fused=True, n_pages=2, hot=3, seed=0, edge=False):
    """A ProtectedKVLayer with `n_pages` frozen pages + `hot` hot tokens."""
    pkv = ProtectedKVConfig(code_name=code_name, page_tokens=page_tokens,
                            fused=fused)
    layer = ProtectedKVLayer(pkv, batch, hkv, dh)
    key = jax.random.PRNGKey(seed)
    t = n_pages * page_tokens + hot
    k = jax.random.normal(key, (batch, t, hkv, dh), jnp.bfloat16)
    v = jax.random.normal(jax.random.fold_in(key, 1),
                          (batch, t, hkv, dh), jnp.bfloat16)
    if edge:
        # absmax saturation + exact-zero rows: the int8 clip/round edges
        k = k.at[:, 0].set(512.0)
        v = v.at[:, 0].set(-512.0)
        k = k.at[:, 1].set(0.0)
        v = v.at[:, 1].set(0.0)
    layer.append(k, v)
    assert layer.n_frozen == n_pages * page_tokens
    assert layer.hot_len == hot
    return layer


def _q(layer, seed=7):
    hq = layer.hkv * 2
    return jax.random.normal(jax.random.PRNGKey(seed),
                             (layer.batch, 1, hq, layer.dh), jnp.bfloat16)


def _fused_vs_streaming(layer, softcap=0.0):
    q = _q(layer)
    fused = layer.attend(q, softcap)
    ref = _attend_paged(q, layer.pages(), softcap)
    return np.asarray(fused), np.asarray(ref)


# ---------------------------------------------------------------------------
# parity: every registry code x {clean, flagged-word, quantized-edge}
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("code_name", sorted(REGISTRY))
def test_fused_bitexact_clean(code_name):
    layer = _mk_layer(code_name)
    fused, ref = _fused_vs_streaming(layer)
    assert np.array_equal(fused, ref), (
        f"fused != streaming on clean pages for {code_name}")


@pytest.mark.parametrize("code_name", sorted(REGISTRY))
def test_fused_bitexact_corrupted(code_name):
    """Inject correctable errors: the fused path consumes pages corrected
    by the scan-gated FBP upstream and must match the streaming corrected
    read bitwise — and corrections must be accounted."""
    code = get_code(code_name)
    layer = _mk_layer(code_name, seed=1)
    changed = layer.inject(asymmetric_adjacent(code.p, 0.002, 0.002), key=3)
    assert changed > 0
    fused, ref = _fused_vs_streaming(layer)
    assert np.array_equal(fused, ref), (
        f"fused != streaming on corrected pages for {code_name}")
    st = layer.stats()
    assert st["detected"] > 0


@pytest.mark.parametrize("code_name", ["wl40_r08", "wl160_r08"])
def test_fused_bitexact_quant_edges(code_name):
    """absmax-saturated and all-zero tokens hit the int8 clip/round edges;
    the in-kernel dequant must still replicate dequantize_tensor exactly."""
    layer = _mk_layer(code_name, edge=True)
    fused, ref = _fused_vs_streaming(layer)
    assert np.array_equal(fused, ref)


@pytest.mark.parametrize("softcap", [0.0, 30.0])
@pytest.mark.parametrize("hot", [0, 3])
def test_fused_bitexact_softcap_hot(softcap, hot):
    """Soft-capped logits and the empty-hot-page boundary (freeze-aligned
    token counts skip the hot update entirely)."""
    layer = _mk_layer("wl40_r08", hot=hot)
    q = _q(layer)
    fused = np.asarray(layer.attend(q, softcap))
    ref = np.asarray(_attend_paged(q, layer.pages(), softcap))
    assert np.array_equal(fused, ref)


def test_fused_no_frozen_pages_hot_only():
    """Before the first freeze there are zero GF pages; the fused path pads
    the page axis to the NP=1 bucket with no-op zero pages and must still
    match the streaming hot-only read bitwise."""
    layer = _mk_layer("wl40_r08", n_pages=0, hot=3)
    fused, ref = _fused_vs_streaming(layer)
    assert np.array_equal(fused, ref)


def test_fused_pallas_kernel_allclose():
    """The Pallas kernel (interpret mode on CPU) keeps fp32 in VMEM instead
    of the streaming path's bf16 page round-trips, so it is allclose — not
    bitwise — against the jnp oracle."""
    layer = _mk_layer("wl40_r08")
    q = _q(layer)
    with use_policy("ref"):
        ref = np.asarray(layer.attend(q, 0.0), np.float32)
    layer._gf_stack = None
    with use_policy("interpret"):
        kern = np.asarray(layer.attend(q, 0.0), np.float32)
    np.testing.assert_allclose(kern, ref, atol=2e-2, rtol=2e-2)


@pytest.mark.parametrize("code_name,page_shape,S", [
    ("wl160_r08", (1, 16, 2, 64), 3),     # engine layout at head_dim 64
    ("wl40_r08", (2, 4, 2, 8), 2),        # 2 digit rows per word group
    ("wl160_r08_gf7", (1, 8, 2, 64), 2),  # GF(7): 3 digits per byte
])
def test_pallas_kernel_slot_layout_allclose(rng, code_name, page_shape, S):
    """Raw operands in the serving engine's per-slot layout (S sub-pages per
    page step, ragged valid counts incl. empty rows and zero pad pages):
    the Pallas kernel (interpreted) against the jnp oracle."""
    from repro.memory.paged import words_for_tensor
    n, k, p, _ = REGISTRY[code_name]
    Bsub, T, Hkv, Dh = page_shape
    B, NP, Hq = S * Bsub, 3, 2 * Hkv
    W = words_for_tensor(page_shape, p, k)
    kp, vp = (jnp.asarray(rng.integers(0, p, (NP, S, W, n)), jnp.int32)
              for _ in range(2))
    ks, vs = (jnp.asarray(rng.uniform(0.01, 0.05, (NP, S)), jnp.float32)
              for _ in range(2))
    valid = rng.integers(0, T + 1, (NP, B))
    valid[-1] = 0                                # a zero pad page step
    q = jnp.asarray(rng.normal(size=(B, 1, Hq, Dh)), jnp.bfloat16)
    hk, hv = (jnp.asarray(rng.normal(size=(B, T, Hkv, Dh)), jnp.bfloat16)
              for _ in range(2))
    hval = jnp.asarray(rng.integers(0, T + 1, B), jnp.int32)
    args = (q, kp, vp, ks, vs, jnp.asarray(valid, jnp.int32), hk, hv, hval)
    kw = dict(p=p, k_info=k, page_shape=page_shape)
    ref = ops.attend_protected(*args, policy="ref", **kw)
    kern = ops.attend_protected(*args, policy="interpret", **kw)
    np.testing.assert_allclose(np.asarray(kern, np.float32),
                               np.asarray(ref, np.float32),
                               atol=2e-2, rtol=2e-2)


def test_fused_off_streams(monkeypatch):
    """fused=False must never touch attend_protected."""
    layer = _mk_layer("wl40_r08", fused=False)
    called = []
    monkeypatch.setattr(ops, "attend_protected",
                        lambda *a, **k: called.append(1))
    out = layer.attend(_q(layer), 0.0)
    assert not called and out.shape == (layer.batch, 1, 2 * layer.hkv,
                                        layer.dh)


def test_np_bucket():
    assert [ops.np_bucket(n) for n in (0, 1, 2, 3, 4, 5, 9)] == \
        [1, 1, 2, 4, 4, 8, 16]


# ---------------------------------------------------------------------------
# KernelPolicy: one policy object, jit-cache-correct overrides
# ---------------------------------------------------------------------------


def test_kernel_policy_resolution():
    assert KernelPolicy("ref").resolve() == "ref"
    assert KernelPolicy("interpret").resolve() == "interpret"
    on_tpu = jax.default_backend() == "tpu"
    assert KernelPolicy("auto").resolve() == (
        "compiled" if on_tpu else "ref")
    assert KernelPolicy("ref").interpret is True
    assert KernelPolicy("compiled").interpret is False
    with pytest.raises(ValueError, match="mode"):
        KernelPolicy("gpu")


def test_use_policy_override_selects_executable():
    """The regression the redesign exists for: resolving the policy inside
    a jitted wrapper caches the FIRST policy's trace; resolving outside
    must let an override switch executables. The ref and interpret modes
    agree numerically, so switching is observed via the dispatch seam."""
    assert current_policy().mode == "auto"
    with use_policy("interpret"):
        assert current_policy().mode == "interpret"
        with use_policy(KernelPolicy("ref")):
            assert current_policy().resolve() == "ref"
        assert current_policy().mode == "interpret"
    assert current_policy().mode == "auto"
    # numeric agreement across modes through the SAME public wrapper
    a = jnp.arange(12, dtype=jnp.int32).reshape(3, 4) % 5
    b = (jnp.arange(20, dtype=jnp.int32).reshape(4, 5) * 3) % 5
    with use_policy("ref"):
        r = ops.gf_matmul(a, b, 5)  # noqa: RPL002  # tiny fixed GF(5) case, far below the int32 bound
    with use_policy("interpret"):
        i = ops.gf_matmul(a, b, 5)  # noqa: RPL002  # tiny fixed GF(5) case, far below the int32 bound
    assert np.array_equal(np.asarray(r), np.asarray(i))


def test_flash_attention_honors_policy():
    """Regression for the hardcoded `interpret=True` default: flash_fwd now
    resolves through the policy (ref/auto off-TPU still interprets, so this
    asserts the resolution seam exists and runs)."""
    from repro.kernels.flash_attention import flash_fwd
    import inspect
    sig = inspect.signature(flash_fwd)
    assert sig.parameters["interpret"].default is None


# ---------------------------------------------------------------------------
# removed aliases: the one-release deprecation window is over; the old
# kwargs now fail loudly, and the converter functions carry the vocabulary
# ---------------------------------------------------------------------------


def test_store_backend_kwarg_removed():
    from repro.kernels.backend import policy_from_store_backend
    from repro.memory import PagedProtectedStore
    with pytest.raises(TypeError, match="backend"):
        PagedProtectedStore("wl40_r08", page_words=8, backend="ref")  # noqa: RPL006  # asserts the kwarg removal
    st = PagedProtectedStore("wl40_r08", page_words=8,
                             policy=policy_from_store_backend("ref"))
    assert st.policy.resolve() == "ref"


def test_pool_backend_kwarg_removed():
    from repro.kernels.backend import policy_from_store_backend
    from repro.memory.pool import ProtectedPagePool
    with pytest.raises(TypeError, match="backend"):
        ProtectedPagePool("wl40_r08", page_words=8, capacity_pages=4,
                          backend="ref")  # noqa: RPL006  # asserts the kwarg removal
    pool = ProtectedPagePool("wl40_r08", page_words=8, capacity_pages=4,
                             policy=policy_from_store_backend("ref"))
    assert pool.policy.resolve() == "ref"


def test_controller_scan_backend_kwarg_removed():
    from repro.kernels.backend import policy_from_scan_backend
    from repro.memory.controller import MemoryController
    with pytest.raises(TypeError, match="scan_backend"):
        MemoryController(scan_backend="host")  # noqa: RPL006  # asserts the kwarg removal
    ctl = MemoryController(policy=policy_from_scan_backend("host"))
    assert ctl.resolved_scan_backend() == "host"
    dev = MemoryController(policy=policy_from_scan_backend("device"))
    assert dev.resolved_scan_backend() == "device"


def test_paged_dict_cache_deprecated():
    """The {"paged": layer} routing warns and unwraps to KVSource
    dispatch with identical output."""
    from repro.configs import get_config
    from repro.nn.layers import attention_apply, init_attention
    from repro.configs.base import LayerSpec
    cfg = get_config("paper_pim").reduced(n_groups=1, d_model=32,
                                          n_heads=4, d_ff=64)
    params = init_attention(jax.random.PRNGKey(0), cfg)
    layer = _mk_layer("wl40_r08", hkv=cfg.n_kv_heads, dh=cfg.head_dim)
    x = jax.random.normal(jax.random.PRNGKey(2), (layer.batch, 1,
                                                  cfg.d_model), jnp.bfloat16)
    spec = LayerSpec(kind="attn")
    pos = jnp.asarray([[layer.n_tokens]] * layer.batch)
    with pytest.warns(DeprecationWarning, match="paged"):
        y_dict, _ = attention_apply(params, x, spec, cfg, positions=pos,
                                    kv_cache={"paged": layer})  # noqa: RPL006  # asserts the deprecation warning
    with warnings.catch_warnings():
        # the KVSource form must NOT warn
        warnings.simplefilter("error", DeprecationWarning)
        y_src, _ = attention_apply(params, x, spec, cfg, positions=pos,
                                   kv_cache=layer)
    assert np.asarray(y_dict).shape == np.asarray(y_src).shape


def test_kv_layer_is_kvsource():
    from repro.serving.engine import BatchedDenseKV, BatchedPagedKV
    assert issubclass(ProtectedKVLayer, KVSource)
    assert issubclass(BatchedPagedKV, KVSource)
    assert issubclass(BatchedDenseKV, KVSource)
    assert ProtectedKVLayer.kind == "protected"
    assert BatchedDenseKV.kind == "dense"
