"""window_compiles: XLA compiles plus persistent-cache loads inside the
window (JAX monitoring events); every program should be ready before
the window opens, so this should read 0."""


def read(ctx):
    return ctx.window_compiles
