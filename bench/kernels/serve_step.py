"""Model operations of served tokens, from the model's shapes alone.

A decoded token: 2 operations per weight of every block's matrices (the
q, k, v and output projections and the three of the gated MLP), 2·d·V for
the output head, and 4·Hq·D per layer for each token of its context
(q·k and p·v). A prompt of S tokens: the blocks' 2 per weight for each of
its tokens, causal attention over 1 + 2 + ... + S tokens a layer, and the
head once, for the position whose logits are served. Padding rows,
recomputation and the protection's own work do not enter.
"""


def block_weights(*, d: int, ff: int, hq: int, hkv: int, dh: int) -> int:
    """Weights of one block's matrices."""
    return d * hq * dh * 2 + d * hkv * dh * 2 + 3 * d * ff


def work(*, contexts: list[int], prompts: list[int], d: int, ff: int,
         layers: int, hq: int, hkv: int, dh: int, vocab: int) -> float:
    """Operations of decoding one token at each context length in
    `contexts` and of prefilling each prompt length in `prompts`."""
    w = layers * block_weights(d=d, ff=ff, hq=hq, hkv=hkv, dh=dh)
    head = 2.0 * d * vocab
    attn = 4.0 * hq * dh * layers
    decode = len(contexts) * (2.0 * w + head) + attn * sum(contexts)
    prefill = sum(2.0 * w * s + attn * s * (s + 1) / 2 + head
                  for s in prompts)
    return decode + prefill
