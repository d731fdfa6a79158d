"""Operations and bytes of the streaming scan, from shapes alone.

One flat scan of a vid reads the vid's resident column once: its rows
and width padded to the kernel's 128-blocks, in float32. Its required
operations are 2·B·N·d for B queries of width d; at B ≤ 32 that is at most
16 operations a byte, far under the v5e's ridge of about 240, so bytes
bound it. The least time of a scan is the larger of bytes over HBM
bandwidth and operations over the bf16 peak.
"""
from __future__ import annotations

BLOCK = 128


def _pad(n: int) -> int:
    return -(-n // BLOCK) * BLOCK


def width(config: dict, vid) -> int:
    return sum(int(config["columns"][c][1]) for c in vid)


def scan_bytes(config: dict, vid) -> int:
    """HBM bytes of one flat scan of ``vid``'s resident column."""
    return _pad(int(config["rows"])) * _pad(width(config, vid)) * 4


def scan_flops(config: dict, vid, batch: int) -> int:
    return 2 * int(batch) * int(config["rows"]) * width(config, vid)


def least_s(config: dict, groups, peaks: dict) -> float:
    """Least device seconds of the flat scans in ``groups`` ((plan
    signature, batch) per plan group, as the program's spans give them)."""
    total = 0.0
    for sig, batch in groups:
        for kind, vid, _ in sig:
            if kind == "flat":
                total += max(scan_bytes(config, vid) / peaks["hbm_bytes_per_s"],
                             scan_flops(config, vid, batch)
                             / peaks["bf16_flops_per_s"])
    return total


def n_scans(groups) -> int:
    return sum(1 for sig, _ in groups for kind, _, _ in sig if kind == "flat")


SCAN_PROGRAM = "streaming_fused_scan"


def scan_share(run) -> float | None:
    """Least time over device time of the window's flat scans, in %.
    None without a trace, without scans, or where the trace's scan
    programs and the spans' flat scans do not pair up one to one."""
    from bench import spec
    from bench import trace as btrace

    if run.trace is None or not run.groups:
        return None
    secs, n = btrace.module_s(run.trace, SCAN_PROGRAM)
    if n == 0 or n != n_scans(run.groups):
        return None
    peaks = spec.peaks(run.device_kind, run.cell.root)
    return 100.0 * least_s(run.cell.config, run.groups, peaks) / secs
