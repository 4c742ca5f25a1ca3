"""device_idle_pct: the share of the traced window in which no
operation ran on the device (1 - union of op intervals / window)."""


def read(ctx):
    if ctx.trace is None or not ctx.trace.ops:
        return None
    return ctx.trace.idle_pct
