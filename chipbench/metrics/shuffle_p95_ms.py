"""End to end: the 95th percentile of client-side latency (call to outputs
on the host) over every call in the window, in ms."""
import numpy as np


def read(ctx):
    return float(np.percentile([(t1 - t0) * 1e3 for t0, t1 in ctx["calls"]], 95))
