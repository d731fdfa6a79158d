"""Median time inside a window flush (the program's ``mint.dispatch``
annotation) with no operation on the first chip, ms (device trace)."""
from bench.program_spans import dispatch_gap_ms


def read(run):
    return dispatch_gap_ms(run.trace)
