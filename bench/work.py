"""The least HBM bytes a request needs, computed from its shapes.

A request reads each distinct bitmap that its queries name once, and its
answers are counts, so no output bytes are counted. Any implementation,
fused or not, padded or not, sharing reads between the queries of a
request or not, has to read at least these bytes, so a share of the
roofline built on them stays under 100%.
"""

from __future__ import annotations


def bitmap_bytes(n_bits: int) -> int:
    """Bytes of one packed bitmap of ``n_bits`` bits."""
    return -(-n_bits // 8)


def count_bytes(n_bits: int, distinct: int) -> int:
    """Counts over expressions that read ``distinct`` bitmaps in all."""
    return distinct * bitmap_bytes(n_bits)
