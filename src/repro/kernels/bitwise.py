"""Fused bulk-bitwise expression kernel (Pallas, TPU target).

This is the TPU-native realization of an Ambit AAP chain: the whole bitwise
expression DAG is evaluated in ONE pass over VMEM-resident uint32 tiles, so
intermediates never travel back to HBM - the analogue of Ambit keeping
operands inside the subarray and eliding copies with RowClone/dead-store
elimination (Sections 3.1.4, 4.2).

Tiling: operands are (rows, words) packed uint32. Blocks of
(BLOCK_ROWS, BLOCK_WORDS) live in VMEM; the grid walks row tiles x word
tiles. BLOCK_WORDS is a multiple of 128 (VREG lane width) and BLOCK_ROWS a
multiple of 8 (sublanes), so tiles map exactly onto (8,128) int32 VREGs and
the VPU executes one logical op per VREG pair per cycle - the arithmetic
intensity is ~#ops/12 bytes, i.e. firmly HBM-bound, which is precisely the
regime Ambit targets (Section 7).
"""

from __future__ import annotations

import functools
from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..core import expr as E

DEFAULT_BLOCK_ROWS = 256
DEFAULT_BLOCK_WORDS = 512


def _expr_kernel(expression: E.Expr, names: Tuple[str, ...]):
    def kernel(*refs):
        *in_refs, o_ref = refs
        env = {nm: r[...] for nm, r in zip(names, in_refs)}
        o_ref[...] = E.eval_expr(expression, env)

    return kernel


@functools.partial(jax.jit,
                   static_argnames=("expression", "names", "block_rows",
                                    "block_words", "interpret"))
def fused_bitwise(expression: E.Expr, names: Tuple[str, ...],
                  *arrays: jnp.ndarray,
                  interpret: bool,
                  block_rows: int = DEFAULT_BLOCK_ROWS,
                  block_words: int = DEFAULT_BLOCK_WORDS) -> jnp.ndarray:
    """Evaluate `expression` over equal-shaped (rows, words) uint32 arrays."""
    rows, words = arrays[0].shape
    br = min(block_rows, rows)
    bw = min(block_words, words)
    grid = (pl.cdiv(rows, br), pl.cdiv(words, bw))
    spec = pl.BlockSpec((br, bw), lambda i, j: (i, j))
    return pl.pallas_call(
        _expr_kernel(expression, names),
        grid=grid,
        in_specs=[spec] * len(arrays),
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((rows, words), jnp.uint32),
        interpret=interpret,
    )(*arrays)


@functools.partial(jax.jit,
                   static_argnames=("expression", "names", "block_rows",
                                    "block_words", "interpret"))
def fused_bitwise_stacked(expression: E.Expr, names: Tuple[str, ...],
                          *arrays: jnp.ndarray,
                          interpret: bool,
                          block_rows: int = DEFAULT_BLOCK_ROWS,
                          block_words: int = DEFAULT_BLOCK_WORDS
                          ) -> jnp.ndarray:
    """Multi-query fusion: evaluate `expression` over ``(queries, rows,
    words)`` uint32 stacks in ONE kernel launch. The leading grid axis
    walks the query dimension, so an epoch of shape-compatible queries
    costs one dispatch instead of one per query - the multi-session
    analogue of the AAP-chain fusion above (banks run concurrent bbops;
    here query tiles share one launch's grid)."""
    queries, rows, words = arrays[0].shape
    br = min(block_rows, rows)
    bw = min(block_words, words)
    grid = (queries, pl.cdiv(rows, br), pl.cdiv(words, bw))
    spec = pl.BlockSpec((1, br, bw), lambda q, i, j: (q, i, j))
    return pl.pallas_call(
        _expr_kernel(expression, names),
        grid=grid,
        in_specs=[spec] * len(arrays),
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((queries, rows, words), jnp.uint32),
        interpret=interpret,
    )(*arrays)
