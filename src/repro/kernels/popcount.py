"""Packed popcount reduction kernel (the paper's `bitcount`, Section 9.1).

``popcount_rows`` counts the set bits of each row of a (..., words)
uint32 array as ONE jitted program, which a profiler trace labels
``jit_popcount_rows:*``: whatever the operand needs to reach its kernel,
the kernel and the final reduce all run inside it. The operand's shape
picks the kernel:

  * one row of more than half a (8, 128) tile of words (the test
    ``bitwise.reads_flat``; every bitmap and selection the runtime
    counts) is read flat, where it lies in HBM, as the fused kernel reads
    it: 1-D blocks of BR x 128 words, sized as ``bitwise.block_shape``
    sizes one operand's. Inside a block, (8, 128) strips of words add
    their population counts into an (8, 128) int32 carry, which
    accumulates into one (8, 128) output block that the grid revisits;
    the program then sums those 1024 partials. The last block may reach
    past the array, where its contents are undefined: only its words
    within the array are read, the last strip's masked.
  * any other operand goes as (rows, words), padded to whole (8, 128)
    tiles. The grid walks (row tiles, word tiles); the word-tile
    dimension is innermost and revisits the same (rows, 1) output block
    - the standard Pallas reduction pattern (sequential grid on TPU makes
    the accumulation race-free). Where the padded word count is not a
    multiple of the word tile, the kernel masks the words beyond the end
    of its last tile (a mask that Python adds only for such shapes).

Counts are exact below 2^31 bits per row: a lane's partial counts at
most 32 bits of every 1024 words.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from . import bitwise as _bitwise

DEFAULT_BLOCK_ROWS = 256
DEFAULT_BLOCK_WORDS = 512

_TILE = (_bitwise.SUBLANES, _bitwise.LANES)
STRIP = _bitwise.SUBLANES * _bitwise.LANES
# strips whose counts one loop step adds, a tree of independent adds
UNROLL = 8


def _popcount_kernel(x_ref, o_ref, *, words: int):
    j = pl.program_id(1)
    x = x_ref[...]
    bw = x.shape[1]
    if words % bw:
        lane = lax.broadcasted_iota(jnp.int32, x.shape, 1)
        x = jnp.where(lane < words - j * bw, x, jnp.uint32(0))
    pc = lax.population_count(x).astype(jnp.int32)
    partial = pc.sum(axis=1, keepdims=True)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = partial

    @pl.when(j != 0)
    def _acc():
        o_ref[...] = o_ref[...] + partial


def _strip_counts(x_ref, start, strips: int, valid: int = STRIP):
    """(8, 128) int32 popcounts of ``strips`` strips from word ``start``,
    of whose last strip only the first ``valid`` words count."""
    counts = []
    for s in range(strips):
        at = start + s * STRIP
        if not isinstance(at, int):
            at = pl.multiple_of(at, STRIP)
        x = x_ref[pl.ds(at, STRIP)].reshape(_TILE)
        if s == strips - 1 and valid < STRIP:
            word = (lax.broadcasted_iota(jnp.int32, _TILE, 0) * _TILE[1]
                    + lax.broadcasted_iota(jnp.int32, _TILE, 1))
            x = jnp.where(word < valid, x, jnp.uint32(0))
        counts.append(lax.population_count(x).astype(jnp.int32))
    while len(counts) > 1:
        counts = [a + b for a, b in zip(counts[::2], counts[1::2])] \
            + counts[len(counts) // 2 * 2:]
    return counts[0]


def _block_counts(x_ref, n: int):
    """(8, 128) int32 popcounts of the block's first ``n`` words."""
    chunk = UNROLL * STRIP
    loops, rest = divmod(n, chunk)
    acc = jnp.zeros(_TILE, jnp.int32)
    if loops:
        acc = lax.fori_loop(
            0, loops,
            lambda i, a: a + _strip_counts(x_ref, i * chunk, UNROLL), acc)
    if rest:
        strips, tail = divmod(rest, STRIP)
        acc = acc + _strip_counts(x_ref, loops * chunk, strips + bool(tail),
                                  tail or STRIP)
    return acc


def _flat_kernel(x_ref, o_ref, *, words: int, block: int):
    j = pl.program_id(0)
    last, rem = divmod(words - 1, block)
    rem += 1                      # words in the last block

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros(_TILE, jnp.int32)

    if rem == block:
        o_ref[...] += _block_counts(x_ref, block)
        return
    if last:
        @pl.when(j < last)
        def _whole():
            o_ref[...] += _block_counts(x_ref, block)

    @pl.when(j == last)
    def _last():
        o_ref[...] += _block_counts(x_ref, rem)


def _popcount_flat(x: jnp.ndarray, interpret) -> jnp.ndarray:
    (words,) = x.shape
    (block,) = _bitwise.block_shape(1, x.shape)
    partials = pl.pallas_call(
        functools.partial(_flat_kernel, words=words, block=block),
        grid=(pl.cdiv(words, block),),
        in_specs=[pl.BlockSpec((block,), lambda j: (j,))],
        out_specs=pl.BlockSpec(_TILE, lambda j: (0, 0)),
        out_shape=jax.ShapeDtypeStruct(_TILE, jnp.int32),
        interpret=interpret,
        name="popcount_rows",
    )(x)
    return partials.sum()


def _popcount_2d(x: jnp.ndarray, interpret, block_rows: int,
                 block_words: int) -> jnp.ndarray:
    rows = x.shape[0]
    x = jnp.pad(x, [(0, -d % t) for d, t in zip(x.shape, _TILE)])
    words = x.shape[1]
    br = min(block_rows, x.shape[0])
    bw = min(block_words, words)
    out = pl.pallas_call(
        functools.partial(_popcount_kernel, words=words),
        grid=(pl.cdiv(x.shape[0], br), pl.cdiv(words, bw)),
        in_specs=[pl.BlockSpec((br, bw), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((br, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((x.shape[0], 1), jnp.int32),
        interpret=interpret,
        name="popcount_rows",
    )(x)
    return out[:rows, 0]


@functools.partial(jax.jit, static_argnames=("block_rows", "block_words",
                                             "interpret"))
def popcount_rows(x: jnp.ndarray, *, interpret: bool,
                  block_rows: int = DEFAULT_BLOCK_ROWS,
                  block_words: int = DEFAULT_BLOCK_WORDS) -> jnp.ndarray:
    """(..., words) uint32 -> (...,) int32 popcounts, exact for any
    ``words``: no word past the array's end is counted. ``block_rows``
    and ``block_words`` tile the (rows, words) kernel only."""
    lead, words = x.shape[:-1], x.shape[-1]
    if _bitwise.reads_flat(x.shape):
        return _popcount_flat(x.reshape(words), interpret).reshape(lead)
    rows = math.prod(lead)
    return _popcount_2d(x.reshape(rows, words), interpret, block_rows,
                        block_words).reshape(lead)
