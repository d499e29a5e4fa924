"""Logical-axis sharding: models annotate tensors with *logical* axis names;
a per-launch rule table maps them to physical mesh axes.

Models call `constrain(x, "batch", None, "d_ff")` — a no-op when no mesh/rules
are active (CPU unit tests), a `with_sharding_constraint` under an active
`use_rules(mesh, rules)` context (dry-run / production launch).

Also home of `decode_sharded`, the data-parallel batched-decode entry point:
NB-LDPC decode is per-codeword independent, so a `shard_map` over the batch
axis runs each device's slice through the full iterative decoder with zero
collectives.
"""
from __future__ import annotations

import threading
from contextlib import contextmanager
from collections.abc import Callable

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_CTX = threading.local()

# Default logical->physical tables. `None` entries mean "replicated".
# A rule value may be a string (one mesh axis) or a tuple of mesh axes.
RULES_SINGLE_POD = {
    "batch": "data",
    "expert": "model",
    "heads": "model",
    "kv_heads": "model",
    "d_ff": "model",
    "d_inner": "model",
    "vocab": "model",
    "kv_seq": None,       # becomes "data" for long-context decode cells
    "seq": None,
    "d_model": None,
    "code_blocks": "model",
}
RULES_MULTI_POD = dict(RULES_SINGLE_POD, batch=("pod", "data"))


def rules_for(mesh: Mesh, *, seq_sharded_kv: bool = False) -> dict:
    rules = dict(RULES_MULTI_POD if "pod" in mesh.axis_names else RULES_SINGLE_POD)
    if seq_sharded_kv:
        # long_500k: batch=1 -> shard the KV-cache sequence dim over `data`
        rules["kv_seq"] = "data"
        rules["batch"] = "pod" if "pod" in mesh.axis_names else None
    return rules


@contextmanager
def use_rules(mesh: Mesh | None, rules: dict | None = None):
    prev = getattr(_CTX, "state", None)
    if mesh is None:
        _CTX.state = None
    else:
        _CTX.state = (mesh, rules if rules is not None else rules_for(mesh))
    try:
        yield
    finally:
        _CTX.state = prev


def active_mesh() -> Mesh | None:
    st = getattr(_CTX, "state", None)
    return st[0] if st else None


def resolve_spec(axes) -> P:
    """Map logical axis names to a PartitionSpec under the active rules."""
    st = getattr(_CTX, "state", None)
    if st is None:
        return P()
    _, rules = st
    out = []
    for a in axes:
        if a is None:
            out.append(None)
        else:
            out.append(rules.get(a))
    return P(*out)


def constrain(x, *axes):
    """with_sharding_constraint by logical axes; no-op without active rules."""
    st = getattr(_CTX, "state", None)
    if st is None:
        return x
    mesh, _ = st
    spec = resolve_spec(axes)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def named_sharding(mesh: Mesh, rules: dict, axes) -> NamedSharding:
    out = []
    for a in axes:
        out.append(None if a is None else rules.get(a))
    return NamedSharding(mesh, P(*out))


# ---------------------------------------------------------------------------
# sharded batch decode
# ---------------------------------------------------------------------------

def data_mesh(axis_name: str = "data") -> Mesh:
    """1-D mesh over every visible device, for batch-parallel decode."""
    return Mesh(np.array(jax.devices()), (axis_name,))


def decode_sharded(code, y, *, mesh: Mesh | None = None,
                   axis_name: str = "data", n_iters: int = 10,
                   llv_scale: float = 4.0, llv_mode: str = "manhattan",
                   early_exit: bool = False, damping: float = 0.0,
                   cn_fbp: Callable | None = None):
    """Shard batched integer decode across devices along the batch axis.

    y: (B, n) received integer words. B is padded to a multiple of the mesh
    size with all-zero words (valid codewords — they converge immediately)
    and the pad is stripped from every output. Decode is per-codeword
    independent, so the shard_map introduces no collectives; each device
    runs the full iterative decoder on its local slice.

    Returns (y_corrected (B, n), DecodeResult) exactly like
    `repro.core.decode.decode_integers`. Wrap calls in `jax.jit` (or use
    `repro.core.protected.decode_stream`) to amortize trace cost on hot
    paths.
    """
    from repro.core.decode import DecodeResult, decode_integers

    if mesh is None:
        mesh = data_mesh(axis_name)
    ndev = mesh.shape[axis_name]
    B = y.shape[0]
    pad = (-B) % ndev
    if pad:
        y = jax.numpy.concatenate(
            [y, jax.numpy.zeros((pad, y.shape[1]), y.dtype)], axis=0)

    def local_decode(y_local):
        return decode_integers(code, y_local, n_iters=n_iters,
                               llv_scale=llv_scale, llv_mode=llv_mode,
                               early_exit=early_exit, damping=damping,
                               cn_fbp=cn_fbp)

    spec = P(axis_name)
    # check_vma=False: outputs are all batch-sharded, and the per-device
    # decode body needs no replication tracking
    y_corr, res = jax.shard_map(
        local_decode, mesh=mesh, in_specs=spec,
        out_specs=(spec, DecodeResult(spec, spec, spec, spec)),
        check_vma=False)(y)
    if pad:
        y_corr = y_corr[:B]
        res = DecodeResult(res.symbols[:B], res.llv_totals[:B],
                           res.detect_fail[:B], res.iterations[:B])
    return y_corr, res


def shard_page(page, mesh: Mesh, axis_name: str = "data"):
    """Place a (page_words, n) protected-store page row-sharded across the
    mesh devices (the word axis is the paged analogue of the batch axis —
    per-word independence means scan/decode over a sharded page introduces
    no collectives). Used by `repro.memory.paged.PagedProtectedStore` so
    device-resident pages live distributed, not replicated."""
    return jax.device_put(page, NamedSharding(mesh, P(axis_name)))


def scan_syndromes_sharded(code, y, *, mesh: Mesh | None = None,
                           axis_name: str = "data",
                           interpret: bool | None = None):
    """Fan the fused scrub syndrome scan across devices along the batch axis.

    y: (B, n) stored level-words -> (B,) bool flagged mask. Like
    `decode_sharded`, B is padded to a mesh-size multiple with all-zero words
    (valid codewords — never flagged) and the pad is stripped; the scan is
    per-word independent, so the shard_map introduces no collectives. Each
    device runs `repro.kernels.ops.scan_syndromes` (the fused Pallas kernel)
    on its local page slice — this is the `MemoryController` device backend's
    multi-device path for paged scrub sweeps.
    """
    from repro.kernels.ops import scan_syndromes

    # the fused kernel accumulates int32: every per-word syndrome sum is
    # bounded by n*(p-1)^2, which must stay below 2^31 on every shard (the
    # MemoryController routes larger codes to its exact int64 host path)
    assert code.n * (code.p - 1) ** 2 < 2 ** 31, (
        f"scan_syndromes_sharded int32 bound exceeded: "
        f"{code.n} * ({code.p}-1)^2 >= 2^31 — use the exact host scan")

    if mesh is None:
        mesh = data_mesh(axis_name)
    ndev = mesh.shape[axis_name]
    B = y.shape[0]
    pad = (-B) % ndev
    if pad:
        y = jax.numpy.concatenate(
            [y, jax.numpy.zeros((pad, y.shape[1]), y.dtype)], axis=0)
    ht = jax.numpy.asarray(code.H.T, jax.numpy.int32)

    def local_scan(y_local):
        return scan_syndromes(y_local, ht, code.p, interpret=interpret)

    spec = P(axis_name)
    flags = jax.shard_map(local_scan, mesh=mesh, in_specs=spec,
                          out_specs=spec, check_vma=False)(y)
    return flags[:B] if pad else flags
