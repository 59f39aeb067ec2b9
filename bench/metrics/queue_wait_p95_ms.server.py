"""Serving session: 95th percentile of the wait from submit to dispatch, from
the session's ``wait_hist`` differenced over the measured window (to within
one 4.4% bin)."""


def read(ctx):
    if not ctx.stats.wait_hist.count:
        return None
    return ctx.stats.wait_p95_ms()
