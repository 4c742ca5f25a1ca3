"""Jitted public wrappers around the Pallas kernels.

Handles padding to lane-aligned tile multiples, backend selection
(the Pallas interpreter on the CPU backend only; every other backend
compiles the kernels for its device), and shape normalization.
These are the entry points the BulkBitwiseEngine's "pallas" backend
uses.
"""

from __future__ import annotations

import functools
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..core import expr as E
from . import bitweaving as _bw
from . import bitwise as _bitwise
from . import popcount as _pc


# The fused kernels take a multi-row operand as (rows, words), padded to
# whole (sublane, lane) tiles. A one-row operand of more than half a
# LANE_TILE goes to the kernel as its flat (words,) array, read in place:
# HBM stores such a 1-D uint32 array in tiles of LANE_TILE words, which
# hold the bytes of one (8, 128) tile. A shorter one (XLA tiles it in 128
# to 512 words), and any one-row operand in a stack of queries, is padded
# to whole LANE_TILEs and viewed lane-dense, as (words / 128, 128).
FUSED_TILE = (_bitwise.SUBLANES, _bitwise.LANES)
LANE_TILE = _bitwise.SUBLANES * _bitwise.LANES


def fused_operand_bytes(rows: int, words: int) -> int:
    """Bytes of one (rows, words) uint32 operand as the fused kernel
    receives it: whole ``LANE_TILE``s for one row, else whole
    ``FUSED_TILE`` tiles."""
    if rows == 1:
        return 4 * -(-words // LANE_TILE) * LANE_TILE
    tr, tw = FUSED_TILE
    return 4 * -(-rows // tr) * tr * -(-words // tw) * tw


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


def _pad_to(x: jnp.ndarray, mults) -> jnp.ndarray:
    pads = []
    for dim, mult in zip(x.shape, mults):
        rem = (-dim) % mult
        pads.append((0, rem))
    if any(p[1] for p in pads):
        x = jnp.pad(x, pads)
    return x


def _rows_words(shape) -> tuple:
    return (int(np.prod(shape[:-1])) if shape[:-1] else 1), shape[-1]


def _to_kernel(a: jnp.ndarray, stacked: bool = False) -> jnp.ndarray:
    """A (..., words) operand in the fused kernel's layout (see
    ``FUSED_TILE``)."""
    rows, words = _rows_words(a.shape)
    if rows > 1:
        return _pad_to(a.reshape(rows, words), FUSED_TILE)
    if not stacked and _bitwise.reads_flat(a.shape):
        return a.reshape(words)
    return _pad_to(a.reshape(words), (LANE_TILE,)).reshape(
        -1, _bitwise.LANES)


def _from_kernel(out: jnp.ndarray, shape) -> jnp.ndarray:
    """The kernel's result for operands of ``shape``, back in that
    shape: the pad words sliced off."""
    rows, words = _rows_words(shape)
    if rows == 1:
        return out.reshape(-1)[:words].reshape(shape)
    return out[:rows, :words].reshape(shape)


def _eval_padded(expression: E.Expr, names,
                 env: Dict[str, jnp.ndarray]) -> jnp.ndarray:
    """Shape-normalized fused evaluation (shared by the public wrapper and
    the accelerator-resident compiled callables; jit-safe, no counters)."""
    arrays = [jnp.asarray(env[n], jnp.uint32) for n in names]
    out = _bitwise.fused_bitwise(expression, tuple(names),
                                 *map(_to_kernel, arrays),
                                 interpret=_interpret())
    return _from_kernel(out, arrays[0].shape)


def _eval_padded_stacked(expression: E.Expr, names,
                         jobs: Dict[str, Sequence[jnp.ndarray]]) -> list:
    """One stacked-grid kernel launch over several queries: ``jobs`` maps
    each name to its per-query operands, all of one shape (..., words).
    Each operand takes the kernel's layout before the stack, so one-row
    operands stack lane-dense. Returns one result per query, in the
    operands' shape."""
    stacks = [jnp.stack([_to_kernel(jnp.asarray(a, jnp.uint32), stacked=True)
                         for a in jobs[n]]) for n in names]
    out = _bitwise.fused_bitwise_stacked(expression, tuple(names), *stacks,
                                         interpret=_interpret())
    shape = jnp.shape(jobs[names[0]][0])
    return [_from_kernel(o, shape) for o in out]


def bitwise_eval(expression: E.Expr,
                 env: Dict[str, jnp.ndarray]) -> jnp.ndarray:
    """Fused bitwise expression over packed uint32 arrays of equal shape."""
    names = tuple(sorted(env.keys()))
    return _eval_padded(expression, names, env)


def bitwise_eval_stacked(expression: E.Expr, names,
                         envs) -> list:
    """Evaluate one expression over a batch of shape-compatible operand
    environments in a single stacked kernel launch. ``envs`` is a list of
    name->(..., words) arrays, all equal-shaped; returns one result array
    per environment."""
    names = tuple(names)
    return _eval_padded_stacked(
        expression, names, {nm: [env[nm] for env in envs] for nm in names})


def popcount(x: jnp.ndarray) -> jnp.ndarray:
    """Per-row popcount: (..., words) uint32 -> (...,) int32, as one
    dispatch of the jitted ``popcount.popcount_rows``, which reads a
    one-row operand flat where it lies and pads any other inside."""
    return _pc.popcount_rows(jnp.asarray(x, jnp.uint32),
                             interpret=_interpret())


def bitweaving_scan(planes: jnp.ndarray, c1: int, c2: int) -> jnp.ndarray:
    """(b, words) bit-sliced planes -> packed (words,) predicate bitvector."""
    planes = jnp.asarray(planes, jnp.uint32)
    b, words = planes.shape
    padded = _pad_to(planes, (1, 128))
    out = _bw.bitweaving_scan(padded, int(c1), int(c2),
                              interpret=_interpret())
    return out[:words]
