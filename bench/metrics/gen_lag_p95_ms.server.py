"""Load generator: 95th percentile of how late a request was sent against its
schedule. A starved generator must not read as a fast server."""
import numpy as np


def read(ctx):
    if ctx.traffic["loop"] != "open" or not ctx.log.sent:
        return None
    lag = np.array(ctx.log.sent) - np.array(ctx.log.due)
    return float(np.percentile(lag, 95)) * 1e3
