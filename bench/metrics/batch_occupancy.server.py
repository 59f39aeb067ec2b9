"""Serving (``api.ServingSession``): real rows over all dispatched rows, from
the ``SessionStats`` counters differenced over the window, in percent."""


def read(ctx):
    real = ctx.counters["dispatched_rows"]
    total = real + ctx.counters["padded_rows"]
    return 100.0 * real / total if total else None
