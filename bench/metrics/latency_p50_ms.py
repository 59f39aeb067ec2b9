"""Median request latency, due time to result, over every request due in the
window; a failed request counts as missing (no value then)."""
import numpy as np


def read(ctx):
    if not len(ctx.latencies_ms):
        return None
    v = float(np.percentile(ctx.latencies_ms, 50))
    return v if np.isfinite(v) else None
