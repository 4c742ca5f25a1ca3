"""sync_ms_per_req: host time blocked in AmbitRuntime.popcount (the
``bench.popcount`` spans: device reduction and the wait for its count),
per request completed in the traced window."""


def read(ctx):
    if ctx.trace is None or not ctx.requests:
        return None
    return ctx.trace.host_ns("bench.popcount") / 1e6 / ctx.requests
