"""device_ms_per_req: summed device time of every operation in the
traced window, per request completed in it."""


def read(ctx):
    if ctx.trace is None or not ctx.trace.ops or not ctx.requests:
        return None
    return ctx.trace.op_ns / 1e6 / ctx.requests
