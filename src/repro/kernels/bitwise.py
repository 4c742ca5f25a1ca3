"""Fused bulk-bitwise expression kernel (Pallas, TPU target).

This is the TPU-native realization of an Ambit AAP chain: the whole bitwise
expression DAG is evaluated in ONE pass over VMEM-resident uint32 tiles, so
intermediates never travel back to HBM - the analogue of Ambit keeping
operands inside the subarray and eliding copies with RowClone/dead-store
elimination (Sections 3.1.4, 4.2).

Tiling: a one-row bitvector of more than 512 words may arrive as its
flat (words,) array, which HBM stores in tiles of 1024 words that hold the
bytes of one (8, 128) tile, so the kernel reads it in place; otherwise
operands arrive as (rows, words), padded to whole (8, 128) tiles, and a
stack of queries as (queries, rows, words). The grid walks blocks of
whole tiles: (BR, BW) with BW = min(words, 512), or BR x 128 flat words,
BR the most rows whose double-buffered blocks, one per operand and one
for the result, fit ``VMEM_BUDGET``. Inside a block the expression runs
over one (8, BW) strip at a time, so its intermediates stay in VREGs
however large the block. The arithmetic intensity is ~#ops/12 bytes,
i.e. firmly HBM-bound, which is precisely the regime Ambit targets
(Section 7).
"""

from __future__ import annotations

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..core import expr as E

SUBLANES = 8
LANES = 128
BLOCK_WORDS = 512
# VMEM for one grid step's blocks, double-buffered: within the 16 MiB
# that a v5e kernel may use by default, with room for the strip's
# intermediates.
VMEM_BUDGET = 12 * 2 ** 20


def reads_flat(shape: Tuple[int, ...]) -> bool:
    """Whether a (..., words) operand of ``shape`` goes to a kernel as its
    flat (words,) array, read in place: one row of more than half a tile
    of SUBLANES x LANES words, as HBM stores such an array in tiles that
    hold the bytes of one (8, 128) tile."""
    return math.prod(shape[:-1]) == 1 and shape[-1] > SUBLANES * LANES // 2


def block_shape(n_operands: int, shape: Tuple[int, ...]) -> Tuple[int, ...]:
    """The block for ``n_operands`` operands of ``shape``, (words,) or
    (rows, words): the most rows of whole tiles, at most the array's,
    whose 2 x (n_operands + 1) blocks fit ``VMEM_BUDGET``."""
    flat = len(shape) == 1
    if flat:
        rows, bw = -(-shape[0] // (SUBLANES * LANES)) * SUBLANES, LANES
    else:
        rows, bw = shape[0], min(BLOCK_WORDS, shape[1])
    fit = VMEM_BUDGET // (2 * (n_operands + 1) * bw * 4)
    br = min(max(SUBLANES, fit // SUBLANES * SUBLANES), rows)
    return (br * bw,) if flat else (br, bw)


def _expr_kernel(expression: E.Expr, names: Tuple[str, ...],
                 block: Tuple[int, ...]):
    # one strip: 8 rows of a 2-D block, or 8 x 128 words of a flat one
    step = SUBLANES * LANES if len(block) == 1 else SUBLANES
    strip_shape = (step, *block[1:])

    def kernel(*refs):
        *in_refs, o_ref = refs

        def strip(i, carry):
            at = pl.ds(pl.multiple_of(i * step, step), step)
            env = {nm: r[at].reshape(SUBLANES, -1)
                   for nm, r in zip(names, in_refs)}
            o_ref[at] = E.eval_expr(expression, env).reshape(strip_shape)
            return carry

        jax.lax.fori_loop(0, block[0] // step, strip, 0)

    return kernel


def _grid_spec(n_operands: int, shape: Tuple[int, ...]):
    block = block_shape(n_operands, shape)
    grid = tuple(pl.cdiv(d, b) for d, b in zip(shape, block))
    return block, grid


@functools.partial(jax.jit,
                   static_argnames=("expression", "names", "interpret"))
def fused_bitwise(expression: E.Expr, names: Tuple[str, ...],
                  *arrays: jnp.ndarray,
                  interpret: bool) -> jnp.ndarray:
    """Evaluate `expression` over equal-shaped (words,) or (rows, words)
    uint32 arrays."""
    shape = arrays[0].shape
    block, grid = _grid_spec(len(arrays), shape)
    spec = pl.BlockSpec(block, lambda *ij: ij)
    return pl.pallas_call(
        _expr_kernel(expression, names, block),
        grid=grid,
        in_specs=[spec] * len(arrays),
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(shape, jnp.uint32),
        interpret=interpret,
        name="fused_bitwise",
    )(*arrays)


@functools.partial(jax.jit,
                   static_argnames=("expression", "names", "interpret"))
def fused_bitwise_stacked(expression: E.Expr, names: Tuple[str, ...],
                          *arrays: jnp.ndarray,
                          interpret: bool) -> jnp.ndarray:
    """Multi-query fusion: evaluate `expression` over ``(queries, rows,
    words)`` uint32 stacks in ONE kernel launch. The leading grid axis
    walks the query dimension, so an epoch of shape-compatible queries
    costs one dispatch instead of one per query - the multi-session
    analogue of the AAP-chain fusion above (banks run concurrent bbops;
    here query tiles share one launch's grid)."""
    shape = arrays[0].shape
    block, grid = _grid_spec(len(arrays), shape[1:])
    spec = pl.BlockSpec((None, *block), lambda q, i, j: (q, i, j))
    return pl.pallas_call(
        _expr_kernel(expression, names, block),
        grid=(shape[0], *grid),
        in_specs=[spec] * len(arrays),
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(shape, jnp.uint32),
        interpret=interpret,
        name="fused_bitwise_stacked",
    )(*arrays)
