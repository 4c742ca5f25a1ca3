"""p95_ms: nearest-rank 95th percentile of the latencies that p50_ms
reads (host clock)."""


def read(ctx):
    return ctx.percentile(ctx.latencies_ms, 0.95)
