"""The least HBM bytes of one Q6 count, computed from its shapes.

The predicate reads each of the 22 planes of Q6's three columns once and
writes its selection once; the popcount reads the selection once. Any
implementation, fused or not, padded or not, has to move at least these
bytes, so a share of the roofline built on them stays under 100%.
"""

from __future__ import annotations

from bench import work

PLANES = 22     # l_shipdate 12 + l_discount 4 + l_quantity 6 (config.json)


def scan_bytes(n_rows: int) -> int:
    """The predicate: every plane read once, the selection written once."""
    return (PLANES + 1) * work.bitmap_bytes(n_rows)


def selection_bytes(scan: int) -> int:
    """One read of the selections of the queries whose ``scan_bytes``
    add up to ``scan``: what their popcounts have to read."""
    return scan // (PLANES + 1)
