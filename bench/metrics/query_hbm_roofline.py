"""query_hbm_roofline: the least HBM bytes of the requests completed in
the traced window (bench/work.py: each distinct bitmap of a request read
once, no output) at the device's peak bandwidth, as a share of the
summed device time of every operation in the window."""


def read(ctx):
    if ctx.trace is None or not ctx.trace.ops or not ctx.query_bytes:
        return None
    least_ns = ctx.query_bytes / ctx.peaks["hbm_bytes_per_s"] * 1e9
    return 100.0 * least_ns / ctx.trace.op_ns
