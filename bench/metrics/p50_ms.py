"""p50_ms: median latency, issue to last count on the host, over every
request completed in the window (host clock)."""


def read(ctx):
    return ctx.percentile(ctx.latencies_ms, 0.50)
