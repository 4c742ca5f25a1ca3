"""req_per_s: requests whose last count reached the host inside the
window, per second of the window (host clock)."""


def read(ctx):
    return ctx.requests / ctx.window_s
