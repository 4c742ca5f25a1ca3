"""frontend_host_ms_per_req: host time inside QueryFrontend.submit /
tick / take_completed (the ``bench.frontend`` spans, which hold the
scheduler's epoch formation and the planner's dispatch), per request
completed in the traced window."""


def read(ctx):
    if ctx.trace is None or not ctx.requests:
        return None
    return ctx.trace.host_ns("bench.frontend") / 1e6 / ctx.requests
