"""Work of the syndrome scan, from the code's shapes alone.

Per word: 2·nnz(H) operations (a multiply and an add per nonzero of H),
and the stored word read once at ⌈n·log2 p⌉ bits; the flag written back
is one byte. The storage dtype, tiling and padding of an implementation
do not enter, so any implementation is held to the same work.
"""
import math


def work(*, words: int, n: int, p: int, nnz_h: int) -> tuple[float, float]:
    """(operations, bytes) of scanning `words` stored words."""
    word_bytes = math.ceil(n * math.log2(p)) / 8
    return 2.0 * nnz_h * words, (word_bytes + 1) * words
