"""Median wait of a window query in the batcher, from its admission to
its flush (the program's ``flush_wait`` spans)."""
import numpy as np


def read(run):
    if run.flush_wait_ms is None or not run.flush_wait_ms.size:
        return None
    return float(np.median(run.flush_wait_ms))
