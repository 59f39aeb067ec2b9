"""PEs: the Spatial convolution layers' share of their roofline, in percent:
over every ``L*:conv.spat`` row of the traced window's ``layers`` table
whose weights are not prefetched across programs, the least time the chip
could take for their FLOPs and bytes (FLOPs over the compute dtype's peak
or bytes over the HBM rate, the larger, per layer) over their device time.
A row whose own share passes 100% is kept: a FLOP or byte count too high,
or work timed in another scope, then shows in the sum."""


def read(ctx):
    t = ctx.trace
    rows = [r for r in (t.layers or []) if r["scope"].endswith(":conv.spat")
            and not r["prefetched"]] if t is not None else []
    spent = sum(r["ms_per_call"] for r in rows)
    if not spent:
        return None
    return 100.0 * sum(r["least_ms"] for r in rows) / spent
