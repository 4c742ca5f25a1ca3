"""Plain reference for tpch_q6_sf30: each Q6 count straight from the
seeded raw column values, not from their planes, with jax.numpy, so that
the check covers the bit-slicing, the comparator, the fused kernel and
the popcount together. Imports nothing of the program.

``control=True`` sums the matching rows in bfloat16, the exactness a
count loses when it is accumulated in a float narrower than the count:
the check must call that incorrect.
"""

from __future__ import annotations

import datetime

import jax
import jax.numpy as jnp

from bench.configs.tpch_q6_sf30 import data as q6_data


def _days(year: int) -> int:
    return (datetime.date(year, 1, 1) - datetime.date(1992, 1, 1)).days


def _selection(v: dict, n_rows, date_lo, date_hi, discount, quantity):
    """Q6's WHERE clause over every row below ``n_rows``: shipdate in
    [DATE, DATE + 1 year), discount within 0.01 of DISCOUNT, quantity
    below QUANTITY."""
    ship, disc, qty = v["l_shipdate"], v["l_discount"], v["l_quantity"]
    w = ship.shape[0]
    row = jnp.arange(w)[:, None] + w * jnp.arange(ship.shape[1])[None, :]
    return ((row < n_rows) & (ship >= date_lo) & (ship < date_hi)
            & (disc >= discount - 1) & (disc <= discount + 1)
            & (qty < quantity))


@jax.jit
def _count(v, *args):
    return jnp.sum(_selection(v, *args).astype(jnp.int32))


@jax.jit
def _count_bf16(v, *args):
    return jnp.sum(_selection(v, *args).astype(jnp.bfloat16))


def answers(seed: int, cfg: dict, keys, control: bool = False) -> dict:
    v = q6_data.raw(seed, cfg)
    count = _count_bf16 if control else _count
    out = {}
    for key in keys:
        _, year, discount, quantity = key
        out[key] = int(float(count(v, cfg["n_rows"], _days(year),
                                   _days(year + 1), discount, quantity)))
    return out
