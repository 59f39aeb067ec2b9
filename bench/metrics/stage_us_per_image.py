"""Serving session: host time spent staging requests on the callers' threads
(validate, int8 quantize, copy; ``SessionStats.stage_ns``) per request
submitted, over the measured window, in microseconds."""


def read(ctx):
    if not ctx.stats.submitted:
        return None
    return ctx.stats.stage_ns / ctx.stats.submitted / 1e3
