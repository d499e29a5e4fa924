"""Ahead-of-time compiles of the served Pallas kernels for a TPU v5e.

The TPU compiler is installed even where no chip is attached: each test
lowers one kernel at serving widths for a described `v5e:2x2` topology and
asserts that the compiled program holds the Mosaic kernel
(`tpu_custom_call`). This catches what interpret mode cannot — block shapes
off the (8, 128) tiling, dtypes the MXU refuses, primitives Mosaic does not
lower, and VMEM overflow — at no chip time. Nothing runs, so these say
nothing about results or speed; the interpret-mode parity tests and
`chip_smoke.py` on the chip cover those.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and under pytest-xdist every worker
imports this file.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest

from repro.core.codes import REGISTRY
from repro.kernels import ops, use_policy
from repro.kernels import flash_attention as fa
from repro.memory.paged import words_for_tensor

KV_PAGE = (1, 16, 8, 64)          # (Bsub, page_tokens, Hkv, head_dim)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                               # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *shapes) -> str:
    """Compile `fn` at `shapes` with every kernel in compiled (Mosaic)
    mode; the wrappers resolve the policy while `fn` is traced."""
    with use_policy("compiled"):
        return jax.jit(fn).lower(*shapes).compile().as_text()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _rows(code_name: str) -> int:
    """Words per page: one protected KV page, or a memory-mode page."""
    n, k, p, _ = REGISTRY[code_name]
    return (words_for_tensor(KV_PAGE, p, k) if code_name == "wl160_r08"
            else 256)


@pytest.mark.parametrize("code_name", ["wl160_r08", "wl1024_r08"])
def test_encode_words_compiles(one_chip, code_name):
    n, k, p, _ = REGISTRY[code_name]
    assert k * (p - 1) ** 2 < 2 ** 31          # int32 accumulator bound
    txt = _compiled_text(
        lambda u, P: ops.encode_words(u, P, p),
        _spec(one_chip, (_rows(code_name), k), jnp.int32),
        _spec(one_chip, (k, n - k), jnp.int32))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("code_name", ["wl160_r08", "wl1024_r08"])
def test_scan_syndromes_compiles(one_chip, code_name):
    n, k, p, _ = REGISTRY[code_name]
    assert n * (p - 1) ** 2 < 2 ** 31          # int32 accumulator bound
    txt = _compiled_text(
        lambda y, ht: ops.scan_syndromes(y, ht, p),
        _spec(one_chip, (_rows(code_name), n), jnp.int32),
        _spec(one_chip, (n, n - k), jnp.int32))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("B", [16, 64])
def test_attend_protected_compiles(one_chip, B):
    """The engine's layout: one 16-token page per slot (S = B, Bsub = 1),
    paper_pim's 32 query / 8 KV heads at head_dim 64."""
    n, k, p, _ = REGISTRY["wl160_r08"]
    W, NP = _rows("wl160_r08"), 4
    _, T, Hkv, Dh = KV_PAGE
    s = lambda shape, dt: _spec(one_chip, shape, dt)
    txt = _compiled_text(
        lambda *a: ops.attend_protected(*a, p=p, k_info=k,
                                        page_shape=KV_PAGE),
        s((B, 1, 32, Dh), jnp.bfloat16),
        s((NP, B, W, n), jnp.int32), s((NP, B, W, n), jnp.int32),
        s((NP, B), jnp.float32), s((NP, B), jnp.float32),
        s((NP, B), jnp.int32),
        s((B, T, Hkv, Dh), jnp.bfloat16), s((B, T, Hkv, Dh), jnp.bfloat16),
        s((B,), jnp.int32))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("dc", [15, 16])
def test_fbp_cn_compiles(one_chip, dc):
    """Check degrees of the rate-0.8, dv=3 registry codes (wl1024_r08's
    largest is 16)."""
    txt = _compiled_text(
        lambda m: ops.fbp_cn(m, 3),
        _spec(one_chip, (64 * 205, dc, 3), jnp.float32))
    assert "tpu_custom_call" in txt


def test_flash_fwd_compiles(one_chip):
    txt = _compiled_text(
        lambda q, k, v: fa.flash_fwd(q, k, v, g=4, scale=0.125, causal=True,
                                     window=0, softcap=0.0),
        _spec(one_chip, (32, 256, 64), jnp.bfloat16),
        _spec(one_chip, (8, 256, 64), jnp.bfloat16),
        _spec(one_chip, (8, 256, 64), jnp.bfloat16))
    assert "tpu_custom_call" in txt


def test_pim_mac_compiles(one_chip):
    """paper_pim's row parallelism of 64: two groups per 128-lane block."""
    txt = _compiled_text(
        lambda x, w: ops.pim_mac(x, w, row_parallelism=64),
        _spec(one_chip, (128, 2048), jnp.int32),
        _spec(one_chip, (2048, 512), jnp.int32))
    assert "tpu_custom_call" in txt
