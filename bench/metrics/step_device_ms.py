"""Executor step: device time of the XLA modules inside the traced window
(averaged over the chips), over the executor calls the session counted in
that window."""


def read(ctx):
    if ctx.trace is None or not ctx.trace.counters["batches"]:
        return None
    return ctx.trace.module_s * 1e3 / ctx.trace.counters["batches"]
