"""Packed popcount reduction kernel (the paper's `bitcount`, Section 9.1).

Input (rows, words) uint32; output (rows, 1) int32 of set bits per row.
Grid walks (row tiles, word tiles); the word-tile dimension is innermost
and revisits the same output block, accumulating partial popcounts - the
standard Pallas reduction pattern (sequential grid on TPU makes the
accumulation race-free).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

DEFAULT_BLOCK_ROWS = 256
DEFAULT_BLOCK_WORDS = 512


def _popcount_kernel(x_ref, o_ref):
    j = pl.program_id(1)
    pc = lax.population_count(x_ref[...]).astype(jnp.int32)
    partial = pc.sum(axis=1, keepdims=True)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = partial

    @pl.when(j != 0)
    def _acc():
        o_ref[...] = o_ref[...] + partial


@functools.partial(jax.jit, static_argnames=("block_rows", "block_words",
                                             "interpret"))
def popcount_rows(x: jnp.ndarray, *, interpret: bool,
                  block_rows: int = DEFAULT_BLOCK_ROWS,
                  block_words: int = DEFAULT_BLOCK_WORDS) -> jnp.ndarray:
    """(rows, words) uint32 -> (rows,) int32 popcounts."""
    rows, words = x.shape
    br = min(block_rows, rows)
    bw = min(block_words, words)
    grid = (pl.cdiv(rows, br), pl.cdiv(words, bw))
    out = pl.pallas_call(
        _popcount_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((br, bw), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((br, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, 1), jnp.int32),
        interpret=interpret,
    )(x)
    return out[:, 0]
