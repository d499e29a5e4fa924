"""Work of the protected paged attention, from the cache's shapes alone.

Per decode query of one layer: 4·Hq·D operations per token it attends to
(q·k and p·v, a multiply and an add each). Bytes: each frozen page that
holds a context's tokens, K and V, read once as its stored codewords at
⌈n·log2 p⌉ bits a word; each hot (not yet frozen) token's K and V read
once in bfloat16; the query read and the output written once in
bfloat16; two float32 scales a page. Pages of no context (padding), the
storage dtype and the dequantization's own arithmetic do not enter, so
any implementation is held to the same work.
"""
import math


def work(*, pages: int, hot_tokens: int, queries: int, words_per_page: int,
         n: int, p: int, page_tokens: int, hq: int, hkv: int,
         dh: int) -> tuple[float, float]:
    """(operations, bytes) of `queries` decode queries over `pages` frozen
    pages and `hot_tokens` hot tokens, summed over layers and steps."""
    word_bytes = math.ceil(n * math.log2(p)) / 8
    tokens = pages * page_tokens + hot_tokens
    ops = 4.0 * hq * dh * tokens
    nbytes = (pages * (2 * words_per_page * word_bytes + 2 * 4)
              + hot_tokens * 2 * hkv * dh * 2
              + queries * 2 * hq * dh * 2)
    return ops, nbytes
