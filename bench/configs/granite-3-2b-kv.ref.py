"""Plain reference of the served granite-3.0-2b-base: weights from the
seed, and a float32 forward over a served sequence.

Shares no code with the program. The layer equations are the published
Granite/Llama block as the configuration runs it (`granite-3-2b-kv.json`:
the four Granite scalars at 1, the attention scale 1/sqrt(head_dim)):

    h = rmsnorm(x) * g1;  q, k, v = h Wq, h Wk, h Wv;  RoPE(q, k)
    x = x + softmax(q k^T / sqrt(d) + causal) v Wo   (GQA: query head i
                                                    reads kv head i // 4)
    h = rmsnorm(x) * g2;  x = x + (silu(h Wg) * (h Wu)) Wd
    logits = (rmsnorm(x) * gf) E^T                     (tied embedding E)

RoPE rotates the two halves of each head (theta^(-i / half), i < half).

The KV cache is read as the served path holds it: a query at decode
position t sees keys and values of the pages frozen by then (positions
below 16 * floor((t + 1) / 16)) quantized on each 16-token page, and the
rest (the hot row) as computed. Prompt positions attend to the prompt
unquantized, as a prefill does. `kv` picks the page quantization: `int8`
(absmax over the page, the configuration's), or for a control `int4` or
`fp8` (e4m3, scaled to the page's absmax).

Weights: every matrix is drawn on a grid of 255 levels, uniform with
standard deviation `initializer_range` (the source's 0.02), from a key per
(seed, leaf, layer) and made in bfloat16, the type they are served in;
the norms' gains are 1. The program is handed the same numbers stacked
over layers (`stacked_weights`), and the reference draws one layer at a
time (`layer_weights`), so it needs one layer's room.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def shapes(cfg: dict) -> dict[str, tuple[int, int]]:
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    hq = cfg["num_attention_heads"] * cfg["head_dim"]
    hkv = cfg["num_key_value_heads"] * cfg["head_dim"]
    return {"wq": (d, hq), "wk": (d, hkv), "wv": (d, hkv), "wo": (hq, d),
            "w_gate": (d, f), "w_up": (d, f), "w_down": (f, d)}


def _key(seed: int, *fold: int):
    key = jax.random.PRNGKey(int(seed) & 0x7FFFFFFF)
    for f in (int(seed) >> 31, *fold):
        key = jax.random.fold_in(key, f)
    return key


def weight_keys(seed: int) -> dict:
    """The seed's keys as data: one per matrix of a block (each folded
    with the layer index) and one for the embedding. Passed to the jitted
    draws as arguments, so one executable serves every seed."""
    return {"layers": jnp.stack([jax.random.key_data(_key(seed, 20, i))
                                 for i in range(7)]),
            "embed": jax.random.key_data(_key(seed, 21))}


def _grid(key, shape, std: float):
    """Uniform on 255 levels with standard deviation `std`, in bfloat16;
    integer draws and one multiply, so every program that draws it gets
    the same bits."""
    step = std * math.sqrt(3.0) / 127.0
    q = jax.random.randint(key, shape, -127, 128, jnp.int32)
    return (q.astype(jnp.float32) * jnp.float32(step)).astype(jnp.bfloat16)


def _items(cfg: dict) -> tuple:
    keys = ("hidden_size", "intermediate_size", "num_attention_heads",
            "num_key_value_heads", "head_dim", "num_hidden_layers",
            "initializer_range", "vocab_size")
    return tuple((k, cfg[k]) for k in keys)


def _layer(cfg_items, keys, layer):
    cfg = dict(cfg_items)
    return {name: _grid(jax.random.fold_in(
                jax.random.wrap_key_data(keys[i]), layer), shp,
                cfg["initializer_range"])
            for i, (name, shp) in enumerate(shapes(cfg).items())}


@functools.partial(jax.jit, static_argnums=(0,))
def _layer_jit(cfg_items, keys, layer):
    return _layer(cfg_items, keys, layer)


@functools.partial(jax.jit, static_argnums=(0,))
def _stacked_jit(cfg_items, keys):
    n = dict(cfg_items)["num_hidden_layers"]
    return jax.vmap(lambda layer: _layer(cfg_items, keys, layer))(
        jnp.arange(n, dtype=jnp.int32))


@functools.partial(jax.jit, static_argnums=(0,))
def _embedding_jit(cfg_items, key):
    cfg = dict(cfg_items)
    return _grid(jax.random.wrap_key_data(key),
                 (cfg["vocab_size"], cfg["hidden_size"]),
                 cfg["initializer_range"])


def layer_weights(cfg: dict, keys: dict, layer: int) -> dict:
    """Layer `layer`'s seven matrices, bfloat16."""
    return _layer_jit(_items(cfg), keys["layers"], jnp.int32(layer))


def stacked_weights(cfg: dict, keys: dict) -> dict:
    """Every layer's matrices stacked on a leading layer axis, in one
    call on the device."""
    return _stacked_jit(_items(cfg), keys["layers"])


def embedding(cfg: dict, keys: dict):
    """The (vocab, hidden) token embedding, tied to the output head."""
    return _embedding_jit(_items(cfg), keys["embed"])


# ---------------------------------------------------------------------------
# the forward
# ---------------------------------------------------------------------------


def _rmsnorm(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, pos, theta):
    """x (B, L, H, D); rotate the halves of each head by position."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos.astype(jnp.float32)[..., None] * freqs          # (B, L, half)
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def page_quantize(x, kv: str, page: int):
    """x (B, L, H, D) quantized on each `page`-token page, with the scale
    from the page's largest magnitude."""
    B, L, H, D = x.shape
    xp = x.reshape(B, L // page, page * H * D)
    amax = jnp.max(jnp.abs(xp), axis=-1, keepdims=True)
    if kv == "fp8":
        scale = jnp.maximum(amax, 1e-30) / 448.0
        q = (xp / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    else:
        top = {"int8": 127.0, "int4": 7.0}[kv]
        scale = jnp.maximum(amax, 1e-30) / top
        q = jnp.clip(jnp.round(xp / scale), -top, top)
    return (q * scale).reshape(B, L, H, D)


def _mm(a, b, dtype):
    if dtype == jnp.float32:
        return jnp.matmul(a, b.astype(jnp.float32), precision=HIGHEST)
    return jnp.matmul(a.astype(dtype), b.astype(dtype),
                      preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("hp", "kv", "dtype"))
def layer_forward(x, w, prompt_len, *, hp: tuple, kv: str, dtype):
    """One block over (B, L) sequences. x (B, L, d) float32; prompt_len
    (B,): positions below it are the prompt. Returns the block's output
    and its K and V as each frozen page holds them (quantized on every
    page, float32)."""
    hq, hkv, dh, page, eps, theta = hp
    B, L, _ = x.shape
    pos = jnp.broadcast_to(jnp.arange(L)[None], (B, L))
    h = _rmsnorm(x, eps)
    q = _rope(_mm(h, w["wq"], dtype).reshape(B, L, hq, dh), pos, theta)
    k = _rope(_mm(h, w["wk"], dtype).reshape(B, L, hkv, dh), pos, theta)
    v = _mm(h, w["wv"], dtype).reshape(B, L, hkv, dh)
    kq, vq = page_quantize(k, kv, page), page_quantize(v, kv, page)
    g = hq // hkv
    qg = q.reshape(B, L, hkv, g, dh)

    def scores(kk):
        if dtype == jnp.float32:
            s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, kk, precision=HIGHEST)
        else:
            s = jnp.einsum("bqhgd,bkhd->bhgqk", qg.astype(dtype),
                           kk.astype(dtype),
                           preferred_element_type=jnp.float32)
        return s / math.sqrt(dh)

    t = jnp.arange(L)
    frozen = page * ((t + 1) // page)                        # per query
    decode = t[None, :] >= prompt_len[:, None]               # (B, Lq)
    use_q = decode[:, :, None] & (t[None, None, :] < frozen[None, :, None])
    causal = t[None, :] <= t[:, None]
    s = jnp.where(use_q[:, None, None], scores(kq), scores(k))
    s = jnp.where(causal[None, None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    pq = jnp.where(use_q[:, None, None], p, 0.0)
    pr = p - pq

    def mix(pp, vv):
        if dtype == jnp.float32:
            return jnp.einsum("bhgqk,bkhd->bqhgd", pp, vv, precision=HIGHEST)
        return jnp.einsum("bhgqk,bkhd->bqhgd", pp.astype(dtype),
                          vv.astype(dtype),
                          preferred_element_type=jnp.float32)

    att = (mix(pr, v) + mix(pq, vq)).reshape(B, L, hq * dh)
    x = x + _mm(att, w["wo"], dtype)
    h = _rmsnorm(x, eps)
    y = jax.nn.silu(_mm(h, w["w_gate"], dtype)) * _mm(h, w["w_up"], dtype)
    return x + _mm(y, w["w_down"], dtype), kq, vq


@jax.jit
def _embed(emb, tokens):
    return jnp.take(emb, tokens, axis=0).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("eps", "dtype"))
def head_logits(x, emb, *, eps, dtype):
    """(B, L, vocab) float32 logits of the final hidden states."""
    return _mm(_rmsnorm(x, eps), emb.T, dtype)


def hparams(cfg: dict, page: int) -> tuple:
    return (cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], page, cfg["rms_norm_eps"], cfg["rope_theta"])


def final_hidden(cfg: dict, seed: int, tokens, prompt_len, *, page: int,
                 kv: str = "int8", dtype=jnp.float32, on_layer=None):
    """(B, L, d) hidden states after the last block, one layer at a time
    with its weights drawn again from the seed. tokens (B, L) int32.
    `on_layer(layer, kq, vq)` is handed each block's quantized K and V
    (B, L, Hkv, D)."""
    hp = hparams(cfg, page)
    keys = weight_keys(seed)
    emb = embedding(cfg, keys)
    x = _embed(emb, tokens)
    for layer in range(cfg["num_hidden_layers"]):
        w = layer_weights(cfg, keys, layer)
        x, kq, vq = layer_forward(x, w, prompt_len, hp=hp, kv=kv,
                                  dtype=dtype)
        if on_layer is not None:
            on_layer(layer, kq, vq)
        del w, kq, vq
    return x, emb
