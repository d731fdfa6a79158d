"""The streaming scan's share of its roofline, %: least time of the
window's flat scans (bench/roofline.py) over their programs' device time
in the trace."""
from bench.roofline import scan_share


def read(run):
    return scan_share(run)
