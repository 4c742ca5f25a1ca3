"""wau_16m: daily activity bitmaps and one attribute bitmap over
``n_users`` users, made on the device from the seed, and the Section 8.1
queries over them: each week is the OR of its 7 daily bitmaps, inside
the query."""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

from bench import loadgen, work

WORD = 32


def names(cfg: dict) -> List[str]:
    """``day0`` is the oldest resident day, ``day{n-1}`` the latest."""
    return [f"day{d}" for d in range(cfg["daily_bitmaps"])] + \
        [f"attr{k}" for k in range(cfg["attribute_bitmaps"])]


def words(cfg: dict) -> int:
    n = cfg["n_users"]
    if n % (WORD * 128):
        raise ValueError("n_users must be a multiple of 4096 (whole "
                         "lane rows of packed words)")
    return n // WORD


def week_days(cfg: dict, back: int) -> Tuple[str, ...]:
    """The daily bitmaps of the week ``back`` weeks before the latest
    (0 is the latest week), oldest day first."""
    per, last = cfg["days_per_week"], cfg["daily_bitmaps"]
    first = last - per * (back + 1)
    if first < 0:
        raise ValueError(f"week {back} back is older than the "
                         f"{last} resident days")
    return tuple(f"day{d}" for d in range(first, first + per))


@functools.lru_cache(maxsize=None)
def _make(n_bitmaps: int, n_words: int):
    def make(key):
        return tuple(jax.random.bits(jax.random.fold_in(key, i),
                                     (n_words,), jnp.uint32)
                     for i in range(n_bitmaps))
    return jax.jit(make)


def raw(seed: int, cfg: dict) -> Dict[str, jax.Array]:
    """Every bitmap as packed uint32 words, in one jitted call."""
    nms = names(cfg)
    arrays = _make(len(nms), words(cfg))(loadgen.device_key(seed))
    return dict(zip(nms, arrays))


build = raw     # the program is served the raw bitmaps


@dataclasses.dataclass(frozen=True)
class Query:
    key: Tuple
    program: Tuple          # queries with equal programs share a kernel
    operands: Tuple[str, ...]


def queries(parts, params: dict, cfg: dict) -> List[Query]:
    """One request over the past ``weeks`` weeks: the count of users
    active in every one of them, and/or for each of them the count of
    users active that week and holding the attribute."""
    w = int(params["weeks"])
    weeks = [week_days(cfg, back) for back in range(w)]
    out = []
    for part in parts:
        if part == "all_weeks":
            out.append(Query(("all_weeks", w), ("and_of_weeks", w),
                             sum(weeks[::-1], ())))
        elif part == "week_and_attribute":
            out.extend(Query(("week_and_attribute", back),
                             ("week_and_attr",), weeks[back] + ("attr0",))
                       for back in reversed(range(w)))
        else:
            raise ValueError(f"unknown part {part!r}")
    return out


def program_examples(parts, values: dict, cfg: dict) -> List[Tuple]:
    """(query, most queries of its program in one request) for every
    program that a mix with these parameter ``values`` can reach."""
    best = {}
    for w in values["weeks"]:
        qs = queries(parts, {"weeks": w}, cfg)
        for q in qs:
            n = sum(r.program == q.program for r in qs)
            if n > best.get(q.program, (q, 0))[1]:
                best[q.program] = (q, n)
    return list(best.values())


def _week(var, per: int):
    expr = var[0]
    for v in var[1:per]:
        expr = expr | v
    return expr


def plan(handles: dict, query: Query):
    """(expression, env) for ``QueryFrontend.submit``, over positional
    variables, so that every query of one program shares one compiled
    kernel."""
    from repro.core import Expr
    var = [Expr.var(f"d{i}") for i in range(len(query.operands))]
    env = {f"d{i}": handles[nm] for i, nm in enumerate(query.operands)}
    if query.program[0] == "and_of_weeks":
        per = len(var) // query.program[1]
        expr = _week(var, per)
        for k in range(1, query.program[1]):
            expr = expr & _week(var[k * per:], per)
        return expr, env
    return _week(var, len(var) - 1) & var[-1], env


def load(rt, data: dict, cfg: dict) -> dict:
    from repro.core import BitVector
    return {nm: rt.put(BitVector(arr, cfg["n_users"]), name=nm)
            for nm, arr in data.items()}


def least_bytes(request: List[Query], cfg: dict) -> int:
    """Each distinct bitmap of the request read once."""
    return work.count_bytes(cfg["n_users"],
                            len({nm for q in request for nm in q.operands}))
