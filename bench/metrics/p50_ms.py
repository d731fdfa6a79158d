"""Median latency of the window's queries, due time to done (host clock)."""
import numpy as np


def read(run):
    return float(np.median(run.latency_ms))
