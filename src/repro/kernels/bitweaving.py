"""BitWeaving-V predicate scan kernel (Section 8.2).

Evaluates `c1 <= v <= c2` over a bit-sliced column: plane i of the input
holds bit (b-1-i) (MSB first) of every value, packed 32 values per uint32
word. The comparison runs MSB->LSB keeping three packed masks (gt, lt, eq)
per constant - exactly the BitWeaving algorithm, where every step is a bulk
bitwise op (the workload Ambit accelerates; here fused into one VMEM pass).

The plane loop (b <= 32) is unrolled statically inside the kernel, so the
entire predicate costs one HBM read of the planes and one write of the
result bitvector: arithmetic intensity ~6b ops / (4b+4) bytes/word, still
memory-bound but ~32x less traffic than scanning 32-bit values.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK_WORDS = 512


def _bw_kernel(b: int, c1: int, c2: int):
    def kernel(p_ref, o_ref):
        ones = jnp.uint32(0xFFFFFFFF)
        zero = jnp.uint32(0)
        shape = p_ref[0, :].shape

        def cmp(const):
            gt = jnp.zeros(shape, jnp.uint32)
            lt = jnp.zeros(shape, jnp.uint32)
            eq = jnp.full(shape, ones)
            for i in range(b):
                cbit = (const >> (b - 1 - i)) & 1
                p = p_ref[i, :]
                if cbit:
                    lt = lt | (eq & ~p)
                else:
                    gt = gt | (eq & p)
                eq = eq & ~(p ^ (ones if cbit else zero))
            return gt, lt, eq

        gt1, lt1, eq1 = cmp(c1)
        gt2, lt2, eq2 = cmp(c2)
        o_ref[...] = ((gt1 | eq1) & (lt2 | eq2)).reshape(o_ref.shape)

    return kernel


@functools.partial(jax.jit, static_argnames=("c1", "c2", "block_words",
                                             "interpret"))
def bitweaving_scan(planes: jnp.ndarray, c1: int, c2: int, *,
                    interpret: bool,
                    block_words: int = DEFAULT_BLOCK_WORDS) -> jnp.ndarray:
    """(b, words) uint32 planes -> (words,) packed predicate bitvector."""
    b, words = planes.shape
    bw = min(block_words, words)
    grid = (pl.cdiv(words, bw),)
    out = pl.pallas_call(
        _bw_kernel(b, c1, c2),
        grid=grid,
        in_specs=[pl.BlockSpec((b, bw), lambda j: (0, j))],
        out_specs=pl.BlockSpec((1, bw), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((1, words), jnp.uint32),
        interpret=interpret,
        name="bitweaving_scan",
    )(planes)
    return out[0]
