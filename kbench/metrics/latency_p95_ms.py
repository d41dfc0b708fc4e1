"""95th percentile over all requests in the window of the time from a
request's being due (its orders on the host) to its result on the host."""
import numpy as np


def read(ctx):
    lat = ctx.window.latencies
    return float(np.percentile(lat, 95)) * 1e3 if lat else None
