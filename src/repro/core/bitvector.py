"""Packed bitvector container used throughout the framework.

Bits are packed little-endian-within-word into uint32 lanes (32x denser than
bool tensors; the TPU analogue of Ambit's 65,536-bit DRAM row operands).
The trailing dimension is padded to a multiple of LANE_WORDS (128) so tiles
are VREG-aligned on TPU, mirroring the paper's requirement that bbop sizes
are multiples of the DRAM row size (Section 5.1/5.3) - residues are padded
with zeros exactly as the paper prescribes ("pad with dummy data").
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

WORD = 32
LANE_WORDS = 128  # pad packed words to a multiple of one VREG lane row

Array = Union[np.ndarray, jax.Array]


def padded_words(n_bits: int) -> int:
    words = (n_bits + WORD - 1) // WORD
    return ((words + LANE_WORDS - 1) // LANE_WORDS) * LANE_WORDS


def pack_bits(bits: Array) -> jnp.ndarray:
    """bool (..., n) -> packed uint32 (..., padded_words(n)). Bit i of word w
    holds element w*32+i (little-endian within word)."""
    bits = jnp.asarray(bits, jnp.uint32)
    n = bits.shape[-1]
    words = padded_words(n)
    pad = words * WORD - n
    if pad:
        bits = jnp.pad(bits, [(0, 0)] * (bits.ndim - 1) + [(0, pad)])
    bits = bits.reshape(bits.shape[:-1] + (words, WORD))
    shifts = jnp.arange(WORD, dtype=jnp.uint32)
    return (bits << shifts).sum(-1, dtype=jnp.uint32)


def unpack_bits(words: Array, n_bits: Optional[int] = None) -> jnp.ndarray:
    """packed uint32 (..., w) -> bool (..., n_bits or w*32)."""
    words = jnp.asarray(words, jnp.uint32)
    shifts = jnp.arange(WORD, dtype=jnp.uint32)
    bits = (words[..., None] >> shifts) & jnp.uint32(1)
    bits = bits.reshape(words.shape[:-1] + (words.shape[-1] * WORD,))
    if n_bits is not None:
        bits = bits[..., :n_bits]
    return bits.astype(jnp.bool_)


@dataclasses.dataclass
class BitVector:
    """A logical n_bits-long bitvector stored packed. Rows dimension allows
    batches of bitvectors ((rows, words) layout = rows of an Ambit subarray).
    """

    data: jnp.ndarray  # uint32, (..., words)
    n_bits: int

    @staticmethod
    def from_bits(bits: Array) -> "BitVector":
        bits = jnp.asarray(bits)
        return BitVector(pack_bits(bits), bits.shape[-1])

    @staticmethod
    def zeros(n_bits: int, rows: tuple = ()) -> "BitVector":
        return BitVector(
            jnp.zeros(rows + (padded_words(n_bits),), jnp.uint32), n_bits)

    @staticmethod
    def ones(n_bits: int, rows: tuple = ()) -> "BitVector":
        words = padded_words(n_bits)
        data = jnp.full(rows + (words,), 0xFFFFFFFF, jnp.uint32)
        return BitVector(_mask_tail(data, n_bits), n_bits)

    def bits(self) -> jnp.ndarray:
        return unpack_bits(self.data, self.n_bits)

    @property
    def words(self) -> int:
        return self.data.shape[-1]

    @property
    def nbytes(self) -> int:
        return int(np.prod(self.data.shape)) * 4

    def popcount(self) -> jnp.ndarray:
        return jax.lax.population_count(self.data).sum(-1).astype(jnp.int32)

    def __and__(self, o: "BitVector") -> "BitVector":
        return BitVector(self.data & o.data, self.n_bits)

    def __or__(self, o: "BitVector") -> "BitVector":
        return BitVector(self.data | o.data, self.n_bits)

    def __xor__(self, o: "BitVector") -> "BitVector":
        return BitVector(self.data ^ o.data, self.n_bits)

    def __invert__(self) -> "BitVector":
        return BitVector(_mask_tail(~self.data, self.n_bits), self.n_bits)

    def andnot(self, o: "BitVector") -> "BitVector":
        """self & ~other (set difference)."""
        return BitVector(self.data & ~o.data, self.n_bits)


def _mask_tail(data: jnp.ndarray, n_bits: int) -> jnp.ndarray:
    """Zero the padding bits beyond n_bits (keeps popcounts exact)."""
    words = data.shape[-1]
    if n_bits == words * WORD:
        return data
    full_words = n_bits // WORD
    rem = n_bits % WORD
    idx = jnp.arange(words, dtype=jnp.uint32)
    word_mask = jnp.where(
        idx < full_words, jnp.uint32(0xFFFFFFFF),
        jnp.where(idx == full_words,
                  jnp.uint32((1 << rem) - 1 if rem else 0), jnp.uint32(0)))
    return data & word_mask
