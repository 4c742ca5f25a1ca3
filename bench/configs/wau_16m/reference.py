"""Plain reference for wau_16m: each count straight from the seeded
bitmaps with jax.numpy. Imports nothing of the program.

``control=True`` sums the per-word popcounts in bfloat16, the exactness
a count loses when it is accumulated in a float narrower than the count:
the check must call that incorrect. (Float32 holds every whole number up
to 2^24, so at 2^24 users a float32 sum is still exact and breaks
nothing.)
"""

from __future__ import annotations

from functools import reduce

import jax
import jax.numpy as jnp

from bench.configs.wau_16m import data as wau_data


@jax.jit
def _count(words):
    return jnp.sum(jax.lax.population_count(words).astype(jnp.int32))


@jax.jit
def _count_bf16(words):
    return jnp.sum(jax.lax.population_count(words).astype(jnp.bfloat16))


def _select(bitmaps: dict, key, cfg: dict):
    def week(back):
        return reduce(jnp.bitwise_or,
                      [bitmaps[d] for d in wau_data.week_days(cfg, back)])
    if key[0] == "all_weeks":
        return reduce(jnp.bitwise_and, [week(b) for b in range(key[1])])
    return week(key[1]) & bitmaps["attr0"]


def answers(seed: int, cfg: dict, keys, control: bool = False) -> dict:
    bitmaps = wau_data.raw(seed, cfg)
    out = {}
    for key in keys:
        sel = _select(bitmaps, key, cfg)
        out[key] = int(float(_count_bf16(sel))) if control \
            else int(_count(sel))
    return out
