"""The request generator for serving mixes: reads a mix's data file, draws
from a seed.

A serving mix (`bench/traffic/<name>.json`, `"kind": "requests"`) gives a
prompt-length and an output-length distribution and the size of a block.
The stream is cut into blocks of `block` requests. Every seed gets the
same sizes in each block, drawn once from the mix's own `sizes_seed`; the
run's seed only orders them within the block and draws the prompt ids. So
two seeds do the same work, in another order and on other tokens.

A distribution is a dict with `dist` and, per kind:

- `lognormal`: `median`, `sigma` (of the log), clipped to [`min`, `max`];
- `pareto`: `min` (the scale) and `median` (so the tail index is
  ln 2 / ln(median / min)), clipped to `max`.

Lengths are rounded to whole tokens.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np

from .traffic import rng_for


@dataclasses.dataclass(frozen=True)
class Request:
    index: int                 # position in the stream
    prompt: np.ndarray         # (prompt_len,) int32 token ids
    max_new: int               # output tokens to generate


def draw(spec: dict, rng: np.random.Generator, n: int) -> np.ndarray:
    """`n` whole lengths from the distribution `spec`."""
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "lognormal":
        x = np.exp(math.log(spec["median"]) + spec["sigma"]
                   * rng.standard_normal(n))
    elif spec["dist"] == "pareto":
        alpha = math.log(2.0) / math.log(spec["median"] / lo)
        x = lo * (1.0 - rng.random(n)) ** (-1.0 / alpha)
    else:
        raise ValueError(f"unknown distribution {spec['dist']!r}")
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


def block_sizes(mix: dict, block: int) -> tuple[np.ndarray, np.ndarray]:
    """(prompt lengths, output lengths) of block `block`, the same for
    every seed."""
    r = rng_for(mix["sizes_seed"], 6, block)
    n = int(mix["block"])
    return draw(mix["prompt"], r, n), draw(mix["output"], r, n)


def request(mix: dict, seed: int, index: int, vocab: int) -> Request:
    """Request `index` of the seed's stream."""
    n = int(mix["block"])
    block, i = divmod(index, n)
    prompts, outputs = block_sizes(mix, block)
    j = rng_for(seed, 7, block).permutation(n)[i]
    ids = rng_for(seed, 8, index).integers(0, vocab, int(prompts[j]))
    return Request(index, ids.astype(np.int32), int(outputs[j]))


def max_context(mix: dict) -> int:
    """The longest prompt plus the longest output the mix can draw."""
    return int(mix["prompt"]["max"]) + int(mix["output"]["max"])


def admissions(slots: list, first: int, steps: int, sizes) -> list[int]:
    """Stream indices that a closed loop admits in its next `steps`
    engine steps.

    `slots[b]` is (prompt length, tokens generated, output length) of the
    request in slot b, or None for a free slot; `first` is the next
    stream index and `sizes(i)` request i's (prompt length, output
    length). Each step first admits a request into every free slot (its
    prefill yields the first token), then every busy slot gains a token;
    a request retires at the end of the step that brings it to its
    output length."""
    live = [None if s is None else list(s) for s in slots]
    admitted: list[int] = []
    nxt = first
    for _ in range(steps):
        for b, s in enumerate(live):
            if s is None:
                prompt, out = sizes(nxt)
                live[b] = [prompt, 1, out]
                admitted.append(nxt)
                nxt += 1
        for b, s in enumerate(live):
            s[1] += 1
            if s[1] >= s[2]:
                live[b] = None
    return admitted
