"""Share of the traced window with no operation on the device, %."""
from bench.trace import idle_pct


def read(run):
    return idle_pct(run.trace)
