"""q6_scan_hbm_roofline: the least HBM bytes of the Q6 predicates
answered in the traced window (bench/configs/tpch_q6_sf30/work.py: the
22 planes read once and the selection written once, per query) at the
device's peak bandwidth, as a share of the summed device time of the
fused predicate program's operations (``jit_ambit_query:*``)."""

PROGRAM = "jit_ambit_query:"


def read(ctx):
    if ctx.trace is None or not ctx.query_bytes:
        return None
    op_ns = sum(e - s for label, s, e in ctx.trace.ops
                if label.startswith(PROGRAM))
    if not op_ns:
        return None
    least_ns = ctx.query_bytes / ctx.peaks["hbm_bytes_per_s"] * 1e9
    return 100.0 * least_ns / op_ns
