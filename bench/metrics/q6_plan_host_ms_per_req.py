"""q6_plan_host_ms_per_req: host time spent planning Q6 queries (the
``bench.plan`` spans, which hold the program's ``repro.plan.predicate``
span: the BitWeaving conjunction built over the resident planes), per
request completed in the traced window."""


def read(ctx):
    if ctx.trace is None or not ctx.requests:
        return None
    return ctx.trace.host_ns("bench.plan") / 1e6 / ctx.requests
