"""tpch_q6_sf30: the three columns of TPC-H ``lineitem`` that Q6's
predicate reads, made on the device from the seed with dbgen's value
domains and bit-sliced there (BitWeaving-V planes, MSB first), and Q6's
predicate over them as one count query per request."""

from __future__ import annotations

import dataclasses
import datetime
import functools
import itertools
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

from bench import loadgen
from bench.configs.tpch_q6_sf30 import work

WORD = 32
EPOCH = datetime.date(1992, 1, 1)         # day 0 of l_shipdate
# dbgen's domains (TPC-H v3 Section 4.2.3), inclusive bounds:
# o_orderdate uniform over [1992-01-01, 1998-12-31 - 151 days],
# l_shipdate = o_orderdate + uniform 1-121 days, l_discount 0.00-0.10
# (in cents), l_quantity 1-50.
ORDER_DAYS = (0, (datetime.date(1998, 12, 31) - EPOCH).days - 151)
SHIP_LAG = (1, 121)
DISCOUNT = (0, 10)
QUANTITY = (1, 50)


def day(year: int) -> int:
    """January 1 of ``year`` in days since ``EPOCH``."""
    return (datetime.date(year, 1, 1) - EPOCH).days


def words(cfg: dict) -> int:
    return -(-cfg["n_rows"] // WORD)


def _values(key, n_words: int) -> Dict[str, jax.Array]:
    """Every row's raw values, as (WORD, n_words) int32 arrays: row
    ``WORD * w + j`` is element ``[j, w]``, so that bit-slicing reduces
    over the leading axis."""
    k = [jax.random.fold_in(key, i) for i in range(4)]
    shape = (WORD, n_words)

    def draw(key, lo_hi):
        return jax.random.randint(key, shape, lo_hi[0], lo_hi[1] + 1)

    return {"l_shipdate": draw(k[0], ORDER_DAYS) + draw(k[1], SHIP_LAG),
            "l_discount": draw(k[2], DISCOUNT),
            "l_quantity": draw(k[3], QUANTITY)}


@functools.lru_cache(maxsize=None)
def _raw(n_words: int):
    return jax.jit(functools.partial(_values, n_words=n_words))


def raw(seed: int, cfg: dict) -> Dict[str, jax.Array]:
    """The raw values of every row (rows past ``n_rows`` included), in
    one jitted call."""
    return _raw(words(cfg))(loadgen.device_key(seed))


@functools.lru_cache(maxsize=None)
def _build(n_rows: int, columns: Tuple[Tuple[str, int], ...]):
    n_words = -(-n_rows // WORD)

    def build(key):
        vals = _values(key, n_words)
        row = (jnp.arange(WORD)[:, None]
               + WORD * jnp.arange(n_words)[None, :])
        shifts = jnp.arange(WORD, dtype=jnp.uint32)[:, None]
        out = {}
        for name, bits in columns:
            v = jnp.where(row < n_rows, vals[name], 0).astype(jnp.uint32)
            out[name] = tuple(
                (((v >> (bits - 1 - i)) & 1) << shifts).sum(
                    0, dtype=jnp.uint32) for i in range(bits))
        return out
    return jax.jit(build)


def build(seed: int, cfg: dict) -> Dict[str, Tuple[jax.Array, ...]]:
    """Each column's planes, MSB first, as packed (n_words,) uint32
    arrays, made and sliced on the device in one jitted call; rows past
    ``n_rows`` hold 0."""
    return _build(cfg["n_rows"], tuple(cfg["columns"].items()))(
        loadgen.device_key(seed))


@dataclasses.dataclass(frozen=True)
class Query:
    key: Tuple
    program: Tuple          # Q6's constants are baked into its program
    specs: Tuple[Tuple[str, int, int], ...]


def q6_specs(year: int, discount: int, quantity: int) -> tuple:
    """Q6's predicate as inclusive ``(column, c1, c2)`` ranges:
    ``DATE <= l_shipdate < DATE + 1 year``, ``l_discount`` within a cent
    of ``DISCOUNT``, ``l_quantity < QUANTITY``."""
    return (("l_shipdate", day(year), day(year + 1) - 1),
            ("l_discount", discount - 1, discount + 1),
            ("l_quantity", 0, quantity - 1))


def queries(parts, params: dict, cfg: dict) -> List[Query]:
    """One Q6 count per request, with the request's substitution
    parameters: DATE's year, DISCOUNT in cents, QUANTITY."""
    out = []
    for part in parts:
        if part != "q6":
            raise ValueError(f"unknown part {part!r}")
        key = ("q6", int(params["year"]), int(params["discount"]),
               int(params["quantity"]))
        out.append(Query(key, key, q6_specs(*key[1:])))
    return out


def program_examples(parts, values: dict, cfg: dict) -> List[Tuple]:
    """(query, 1) for every parameter set the mix can draw: each is a
    program of its own."""
    return [(q, 1) for y, d, n in itertools.product(
        values["year"], values["discount"], values["quantity"])
        for q in queries(parts, {"year": y, "discount": d, "quantity": n},
                         cfg)]


def load(rt, data: dict, cfg: dict) -> dict:
    """Every plane resident: column -> its plane handles, MSB first."""
    from repro.core import BitVector
    return {col: [rt.put(BitVector(p, cfg["n_rows"]), name=f"{col}_b{i}")
                  for i, p in enumerate(planes)]
            for col, planes in data.items()}


def plan(handles: dict, query: Query):
    """(expression, env) for ``QueryFrontend.submit``: the program's
    BitWeaving conjunction over the resident planes."""
    from repro.apps.bitweaving_db import conjunction_plan
    return conjunction_plan(handles, query.specs)


def least_bytes(request: List[Query], cfg: dict) -> int:
    """Each query's predicate: its planes read once, its selection
    written once."""
    return len(request) * work.scan_bytes(cfg["n_rows"])
