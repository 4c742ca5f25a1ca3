"""queries_per_epoch: queries the frontend completed inside the window
over the scheduler epochs it ran there (QueryFrontend.report_counters)."""


def read(ctx):
    if not ctx.epochs:
        return None
    return ctx.queries / ctx.epochs
