"""XNOR-popcount binarized matmul kernel (Section 8.4.5, ML on Ambit).

For {-1,+1} vectors packed as bits (1 bit = +1), the dot product is
    a . b = K - 2 * popcount(a XOR b)
so a binary matmul is bulk XOR + popcount - exactly the bulk bitwise
workload Ambit targets (and the basis of XNOR-Net / bit-serial DNNs cited
by the paper).

TPU codesign note: two implementations are offered.
  * VPU path (this kernel): operands stay packed 32x dense; the inner block
    computes (bm, bn, kw) XORs + popcounts on the vector unit. Arithmetic
    intensity grows with bn, so unlike plain bitwise ops this CAN become
    compute-bound; the paper's "processing using memory" insight survives
    as: never unpack in HBM, only inside registers.
  * MXU path (ops.binary_matmul_mxu): unpack tiles to +-1 bf16 in VMEM and
    feed the 128x128 systolic array. On real TPU the MXU's 197 TFLOP/s
    usually beats VPU popcounting for large N; the right choice is
    shape-dependent and benchmarked in benchmarks/kernels_micro.py.

Block shapes: a (bm, kw), b (bn, kw), out (bm, bn); kw = K/32 words. All
dims padded to multiples of (8, 128) lanes by the ops.py wrapper.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

DEFAULT_BLOCK_M = 64
DEFAULT_BLOCK_N = 64
DEFAULT_BLOCK_K_WORDS = 512


def _bmm_kernel(k_bits: int):
    def kernel(a_ref, b_ref, o_ref):
        k = pl.program_id(2)
        a = a_ref[...]  # (bm, kw)
        b = b_ref[...]  # (bn, kw)
        x = a[:, None, :] ^ b[None, :, :]          # (bm, bn, kw)
        pc = lax.population_count(x).astype(jnp.int32).sum(-1)

        @pl.when(k == 0)
        def _init():
            o_ref[...] = jnp.int32(k_bits) - 2 * pc

        @pl.when(k != 0)
        def _acc():
            o_ref[...] = o_ref[...] - 2 * pc

    return kernel


@functools.partial(jax.jit, static_argnames=("k_bits", "block_m", "block_n",
                                             "block_k_words", "interpret"))
def binary_matmul(a_packed: jnp.ndarray, b_packed: jnp.ndarray, k_bits: int,
                  *, interpret: bool,
                  block_m: int = DEFAULT_BLOCK_M,
                  block_n: int = DEFAULT_BLOCK_N,
                  block_k_words: int = DEFAULT_BLOCK_K_WORDS) -> jnp.ndarray:
    """(M, Kw) x (N, Kw) packed uint32 -> (M, N) int32 = K - 2*popcnt(xor).

    Padding bits beyond k_bits must be zero in both operands (0 XOR 0
    contributes nothing)."""
    m, kw = a_packed.shape
    n, kw2 = b_packed.shape
    assert kw == kw2
    bm, bn = min(block_m, m), min(block_n, n)
    bk = min(block_k_words, kw)
    grid = (pl.cdiv(m, bm), pl.cdiv(n, bn), pl.cdiv(kw, bk))
    return pl.pallas_call(
        _bmm_kernel(k_bits),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bn, bk), lambda i, j, k: (j, k)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        interpret=interpret,
        name="binary_matmul",
    )(a_packed, b_packed)
