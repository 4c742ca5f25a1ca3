"""q6_popcount_hbm_roofline: one read of each answered Q6 query's
selection (bench/configs/tpch_q6_sf30/work.py) at the device's peak
bandwidth, as a share of the summed device time of the runtime
popcount's programs: its kernel (``jit_popcount_rows``), its pad to
whole tiles (``jit__pad``) and its reshape to rows (``jit_reshape``,
which XLA lowers as a copy or, for a 1-D array of 5,625,000 words, as a
loop)."""

from bench.configs.tpch_q6_sf30 import work

PROGRAMS = ("jit_popcount_rows:", "jit__pad:", "jit_reshape:")


def read(ctx):
    if ctx.trace is None or not ctx.query_bytes:
        return None
    op_ns = sum(e - s for label, s, e in ctx.trace.ops
                if label.startswith(PROGRAMS))
    if not op_ns:
        return None
    least_ns = (work.selection_bytes(ctx.query_bytes)
                / ctx.peaks["hbm_bytes_per_s"] * 1e9)
    return 100.0 * least_ns / op_ns
