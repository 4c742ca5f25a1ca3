"""q6_peak_hbm_gib: peak_hbm_gib in the Q6 cell: the resident planes
plus the padded operands, selections and popcount copies in flight."""

from bench.metrics.peak_hbm_gib import read  # noqa: F401
