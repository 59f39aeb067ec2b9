"""Executor step: model FLOPs of the images the session dispatched inside the
traced window, over the XLA modules' device time there (summed over the
chips) times the chip's peak for the configuration's compute dtype, in
percent: the whole step's share of the peak while it runs. FLOPs are direct
convolution plus the dense layers, the same work whatever implements it; a
Winograd layer does fewer multiplies, so a fast Winograd path could read
above the direct-convolution peak."""


def read(ctx):
    t = ctx.trace
    if t is None or t.module_s <= 0 or not t.counters["dispatched_rows"]:
        return None
    flops = t.counters["dispatched_rows"] * ctx.flops_per_image
    return 100.0 * flops / (t.module_s * ctx.chips * ctx.peak_ops)
