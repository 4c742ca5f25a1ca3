"""Pure-jnp oracles for every Pallas kernel in this package.

Each function is the semantic ground truth the kernels are validated
against (tests sweep shapes/dtypes and assert exact equality - these are
integer/bit ops, so no tolerance is needed).
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from ..core import expr as E


def bitwise_eval(expression: E.Expr,
                 env: Dict[str, jnp.ndarray]) -> jnp.ndarray:
    """Fused bitwise expression over packed uint32 arrays."""
    return E.eval_expr(expression, env)


def popcount(x: jnp.ndarray) -> jnp.ndarray:
    """Total set bits per row: (rows, words) uint32 -> (rows,) int32."""
    return jax.lax.population_count(x).astype(jnp.int32).sum(-1)


def bitweaving_scan(planes: jnp.ndarray, c1: int, c2: int) -> jnp.ndarray:
    """BitWeaving-V predicate scan: c1 <= v <= c2 (Section 8.2).

    planes: (b, words) uint32 bit-sliced column - plane i holds bit
    (b-1-i) (MSB first) of each of the words*32 values.
    Returns a packed uint32 result bitvector (words,) with bit j set iff
    c1 <= v_j <= c2.
    """
    b = planes.shape[0]
    ones = jnp.uint32(0xFFFFFFFF)

    def cmp(const: int):
        """Returns (gt, lt, eq) packed masks of v <op> const."""
        gt = jnp.zeros_like(planes[0])
        lt = jnp.zeros_like(planes[0])
        eq = jnp.full_like(planes[0], ones)
        for i in range(b):
            cbit = (const >> (b - 1 - i)) & 1
            p = planes[i]
            if cbit:
                lt = lt | (eq & ~p)
            else:
                gt = gt | (eq & p)
            eq = eq & ~(p ^ (ones if cbit else jnp.uint32(0)))
        return gt, lt, eq

    gt1, lt1, eq1 = cmp(c1)
    gt2, lt2, eq2 = cmp(c2)
    ge_c1 = gt1 | eq1
    le_c2 = lt2 | eq2
    return ge_c1 & le_c2


def bitslice(values: jnp.ndarray, bits: int) -> jnp.ndarray:
    """Pack integer column (n,) -> bit-sliced planes (bits, n/32) uint32,
    MSB-first plane order. n must be a multiple of 32."""
    n = values.shape[0]
    assert n % 32 == 0
    v = values.astype(jnp.uint32)
    planes = []
    for i in range(bits):
        bit = (v >> (bits - 1 - i)) & 1
        planes.append(_pack32(bit))
    return jnp.stack(planes)


def _pack32(bits01: jnp.ndarray) -> jnp.ndarray:
    bits01 = bits01.reshape(-1, 32).astype(jnp.uint32)
    shifts = jnp.arange(32, dtype=jnp.uint32)
    return (bits01 << shifts).sum(-1, dtype=jnp.uint32)
