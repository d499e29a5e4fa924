"""The one traffic generator: reads a mix's data file, draws from a seed.

A mix (`bench/traffic/<name>.json`) is parameters only. Every seed gets the
same amount of work, placed differently, so two seeds differ only in where
the work falls:

- `damage` (memory sweeps): which words are hit and how is drawn per sweep
  from the seed; the number of words hit is fixed by `share`, the number
  of wrong symbols in each by `errors_per_word`.
"""
from __future__ import annotations

import json
import os

import numpy as np


def load_mix(root: str, name: str) -> dict:
    with open(os.path.join(root, "traffic", f"{name}.json")) as f:
        mix = json.load(f)
    mix["name"] = name
    return mix


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """Independent numpy stream per (seed, stream...) — any size of seed."""
    return np.random.default_rng([int(seed) & (2 ** 63 - 1), *stream])


def jax_key(seed: int, *fold: int):
    """A JAX key per (seed, fold...) for any whole-number seed, wider than
    32 bits too."""
    import jax
    key = jax.random.PRNGKey(int(seed) & 0x7FFFFFFF)
    for f in (int(seed) >> 31, *fold):
        key = jax.random.fold_in(key, f)
    return key


def damage_plan(mix: dict, seed: int, sweep: int, n_words: int, n: int,
                p: int):
    """Words hit before sweep `sweep`, and per word the columns and the
    nonzero level offsets (mod p) of its `errors_per_word` symbol errors.
    Returns (words (m,), cols (m, e), deltas (m, e))."""
    dmg = mix["damage"]
    m = int(round(dmg["share"] * n_words))
    e = int(dmg["errors_per_word"])
    r = rng_for(seed, 2, sweep)
    words = np.sort(r.choice(n_words, size=m, replace=False))
    cols = np.argsort(r.random((m, n)), axis=1)[:, :e]
    deltas = r.integers(1, p, (m, e))
    return words, cols, deltas

