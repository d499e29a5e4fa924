"""Persistent XLA compilation cache placement.

Entry points (`chip_smoke.py`, `repro.launch.serve`, `repro.launch.train`,
`benchmarks.run`) call `enable_compile_cache()` once, before their first
compile. Where `JAX_COMPILATION_CACHE_DIR` is set, JAX already reads it and
nothing here overrides it; otherwise the cache goes to one fixed directory
inside the checkout (`<repo>/.jax_cache`, git-ignored). The path is part of
each entry's key, so it is never built from a temporary name, a pid or the
time: a directory that moves never hits.
"""
from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                         "..", "..", ".."))
DEFAULT_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get(ENV_VAR) or DEFAULT_DIR
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", path)
    return path
