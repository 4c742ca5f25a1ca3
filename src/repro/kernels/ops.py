"""Jitted public wrappers around the Pallas kernels.

Handles padding to lane-aligned tile multiples, backend selection
(the Pallas interpreter on the CPU backend only; every other backend
compiles the kernels for its device), and shape normalization.
These are the entry points the BulkBitwiseEngine's "pallas" backend and
the model stack use.
"""

from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from ..core import expr as E
from . import binary_matmul as _bmm
from . import bitweaving as _bw
from . import bitwise as _bitwise
from . import popcount as _pc


# The fused kernels take each operand as (rows, words), padded to whole
# (sublane, lane) tiles.
FUSED_TILE = (8, 128)


def fused_operand_bytes(rows: int, words: int) -> int:
    """Bytes of one (rows, words) uint32 operand as the fused kernel
    receives it, after its padding to whole ``FUSED_TILE`` tiles."""
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


def _eval_padded(expression: E.Expr, names,
                 env: Dict[str, jnp.ndarray]) -> jnp.ndarray:
    """Shape-normalized fused evaluation (shared by the public wrapper and
    the accelerator-resident compiled callables; jit-safe, no counters)."""
    arrays = [jnp.asarray(env[n], jnp.uint32) for n in names]
    shape = arrays[0].shape
    lead = shape[:-1]
    words = shape[-1]
    rows = int(np.prod(lead)) if lead else 1
    arrays = [a.reshape(rows, words) for a in arrays]
    padded = [_pad_to(a, FUSED_TILE) for a in arrays]
    out = _bitwise.fused_bitwise(expression, tuple(names), *padded,
                                 interpret=_interpret())
    return out[:rows, :words].reshape(shape)


def _eval_padded_stacked(expression: E.Expr, names,
                         env: Dict[str, jnp.ndarray]) -> jnp.ndarray:
    """(queries, rows, words) stacks -> one stacked-grid kernel launch."""
    arrays = [jnp.asarray(env[n], jnp.uint32) for n in names]
    q, rows, words = arrays[0].shape
    padded = [_pad_to(a, (1, *FUSED_TILE)) for a in arrays]
    out = _bitwise.fused_bitwise_stacked(expression, tuple(names), *padded,
                                         interpret=_interpret())
    return out[:, :rows, :words]


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
    first = jnp.asarray(envs[0][names[0]], jnp.uint32)
    shape = first.shape
    lead, words = shape[:-1], shape[-1]
    rows = int(np.prod(lead)) if lead else 1
    stacked = {
        nm: jnp.stack([jnp.asarray(env[nm], jnp.uint32).reshape(rows, words)
                       for env in envs]) for nm in names}
    out = _eval_padded_stacked(expression, names, stacked)
    return [out[k].reshape(shape) for k in range(len(envs))]


def popcount(x: jnp.ndarray) -> jnp.ndarray:
    """Per-row popcount: (..., words) uint32 -> (...,) int32."""
    x = jnp.asarray(x, jnp.uint32)
    lead = x.shape[:-1]
    words = x.shape[-1]
    rows = int(np.prod(lead)) if lead else 1
    x2 = _pad_to(x.reshape(rows, words), (8, 128))
    out = _pc.popcount_rows(x2, interpret=_interpret())[:rows]
    return out.reshape(lead) if lead else out[0]


def bitweaving_scan(planes: jnp.ndarray, c1: int, c2: int) -> jnp.ndarray:
    """(b, words) bit-sliced planes -> packed (words,) predicate bitvector."""
    planes = jnp.asarray(planes, jnp.uint32)
    b, words = planes.shape
    padded = _pad_to(planes, (1, 128))
    out = _bw.bitweaving_scan(padded, int(c1), int(c2),
                              interpret=_interpret())
    return out[:words]


def binary_matmul(a_packed: jnp.ndarray, b_packed: jnp.ndarray,
                  k_bits: int) -> jnp.ndarray:
    """Packed XNOR-popcount matmul: (M,Kw) x (N,Kw) -> (M,N) int32."""
    a = jnp.asarray(a_packed, jnp.uint32)
    b = jnp.asarray(b_packed, jnp.uint32)
    m, kw = a.shape
    n, _ = b.shape
    ap = _pad_to(a, (8, 128))
    bp = _pad_to(b, (8, 128))
    out = _bmm.binary_matmul(ap, bp, int(k_bits), interpret=_interpret())
    return out[:m, :n]


def binary_matmul_mxu(a_packed: jnp.ndarray, b_packed: jnp.ndarray,
                      k_bits: int) -> jnp.ndarray:
    """MXU alternative: unpack to +-1 and use the systolic array (see
    binary_matmul.py codesign note). Pure-XLA; lowers on any backend."""
    from . import ref
    return ref.binary_matmul_mxu(a_packed, b_packed, k_bits)
