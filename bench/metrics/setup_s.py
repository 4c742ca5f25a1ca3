"""setup_s: process start to window open (host clock): imports, data
made on the device, programs compiled or loaded and warmed, and the
first request of every client issued."""


def read(ctx):
    return ctx.setup_s
