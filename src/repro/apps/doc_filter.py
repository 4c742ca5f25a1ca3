"""Document selection over bit-sliced metadata columns (quality score,
length, language id): q_min <= quality <= q_max AND length >= len_min,
as two BitWeaving scans and one bulk bitwise AND (paper Section 8.2).
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np

from ..core.bitvector import unpack_bits
from ..kernels import ops, ref


@dataclasses.dataclass(frozen=True)
class CorpusMeta:
    """Metadata columns for n documents."""

    quality: np.ndarray  # (n,) uint16, 0..255
    length: np.ndarray   # (n,) uint16, in tokens
    lang: np.ndarray     # (n,) uint16 language id


def synth_corpus_meta(n_docs: int, seed: int = 0) -> CorpusMeta:
    rng = np.random.default_rng(seed)
    return CorpusMeta(
        quality=rng.integers(0, 256, n_docs).astype(np.uint16),
        length=rng.integers(0, 4096, n_docs).astype(np.uint16),
        lang=rng.integers(0, 16, n_docs).astype(np.uint16),
    )


def filter_documents(meta: CorpusMeta, q_min: int, q_max: int,
                     len_min: int) -> np.ndarray:
    """Selection mask via BitWeaving predicate scans + bulk AND.

    Returns a boolean (n_docs,) mask. The scans run on the packed
    bit-sliced columns (32 docs/word); the combine is one fused bitwise
    AND - the exact Section 8.2 pattern."""
    n = len(meta.quality)
    pad = (-n) % 32
    q = np.pad(meta.quality, (0, pad))
    ln = np.pad(meta.length, (0, pad))
    qp = ref.bitslice(jnp.asarray(q), 8)
    lp = ref.bitslice(jnp.asarray(ln), 12)
    sel_q = ops.bitweaving_scan(qp, q_min, q_max)
    sel_l = ops.bitweaving_scan(lp, len_min, 4095)
    both = jnp.asarray(sel_q) & jnp.asarray(sel_l)
    return np.asarray(unpack_bits(both, n))
