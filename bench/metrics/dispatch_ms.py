"""Median time of a window flush in the plan-group engine (the program's
``dispatch`` spans)."""
import numpy as np


def read(run):
    if run.dispatch_ms is None or not run.dispatch_ms.size:
        return None
    return float(np.median(run.dispatch_ms))
