"""95th percentile of the window's query latency, due time to done (host
clock); it wants 200 queries or more in the window."""
import numpy as np


def read(run):
    return float(np.percentile(run.latency_ms, 95))
