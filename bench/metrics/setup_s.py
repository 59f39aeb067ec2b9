"""Set-up time: process start to the first request of the window (imports,
device, weights, DSE and build, session warm-up, warm traffic)."""


def read(ctx):
    return ctx.setup_s
