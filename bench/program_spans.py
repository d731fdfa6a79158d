"""The program's own live spans in the profiler trace of a window.

The program's observer annotates each live span in the trace as
``mint.<span>`` on the thread that ran it. The batcher flushes on the
thread that runs the window, so its ``mint.dispatch`` events sit on the
host line that ``bench.trace.reduce`` keeps. A program without the
annotations leaves none there, and every reading here is then None.
"""
from __future__ import annotations

import numpy as np

from bench.trace import Trace, busy_intervals

DISPATCH = "mint.dispatch"


def idle_inside_ns(tr: Trace, name: str) -> list[float]:
    """For each event ``name`` of the window's host thread that starts
    inside the window, the time within it with no operation on the first
    chip, ns."""
    lo, hi = tr.window
    chip = sorted(tr.ops.items())[0][1]
    out = []
    for e in tr.host:
        if e.name == name and lo <= e.t0 <= hi:
            busy = sum(b - a for a, b in busy_intervals(chip, (e.t0, e.t1)))
            out.append(e.t1 - e.t0 - busy)
    return out


def dispatch_gap_ms(tr: Trace | None) -> float | None:
    """Median device-idle time inside a window flush's ``mint.dispatch``
    annotation, ms; None without a trace, a device operation, or such an
    annotation."""
    if tr is None or not tr.ops:
        return None
    gaps = idle_inside_ns(tr, DISPATCH)
    return float(np.median(gaps)) * 1e-6 if gaps else None
