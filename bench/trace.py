"""The profiler trace of a window, reduced to what the readers take.

``capture`` records the window with ``jax.profiler`` and returns its
events; ``reduce`` keeps

  - the window: the harness's ``bench.window`` annotation;
  - device operations: the ``XLA Ops`` line of each ``/device:TPU:<n>``
    plane, and the jitted programs they ran in (``XLA Modules``);
  - host spans: every event on the host thread that ran the window, the
    harness's ``bench.*`` annotations among them.

Busy time is the union of the device operations' intervals inside the
window, averaged over the chips; an idle gap is a stretch of the window
between them, labelled by the innermost host span around its midpoint.
"""
from __future__ import annotations

import contextlib
import re
import tempfile
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path

import jax

WINDOW = "bench.window"
_DEVICE = re.compile(r"^/device:TPU:\d+$")


@dataclass
class Event:
    plane: str
    line: str
    name: str
    t0: float  # ns
    t1: float  # ns


@dataclass
class Trace:
    window: tuple[float, float]
    ops: dict[str, list[Event]] = field(default_factory=dict)      # per chip
    modules: dict[str, list[Event]] = field(default_factory=dict)  # per chip
    host: list[Event] = field(default_factory=list)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


def events(path: Path) -> list[Event]:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    return [Event(p.name, ln.name, ev.name, float(ev.start_ns),
                  float(ev.start_ns + ev.duration_ns))
            for p in pd.planes for ln in p.lines for ev in ln.events]


@contextlib.contextmanager
def capture():
    """Trace the body; yields a list that holds the events once the body
    has left. The trace files go to a temporary directory and are gone
    after reading."""
    out: list[Event] = []
    with tempfile.TemporaryDirectory(prefix="bench-trace-") as d:
        jax.profiler.start_trace(d)
        try:
            yield out
        finally:
            jax.profiler.stop_trace()
        files = sorted(Path(d).rglob("*.xplane.pb"))
        if files:
            out.extend(events(files[-1]))


def reduce(evs: list[Event]) -> Trace | None:
    """None when the events hold no window."""
    win = [e for e in evs if e.name == WINDOW]
    if not win:
        return None
    w = win[0]
    tr = Trace(window=(w.t0, w.t1))
    for e in evs:
        if _DEVICE.match(e.plane):
            if e.line == "XLA Ops":
                tr.ops.setdefault(e.plane, []).append(e)
            elif e.line == "XLA Modules":
                tr.modules.setdefault(e.plane, []).append(e)
        elif e.plane == w.plane and e.line == w.line and e is not w:
            tr.host.append(e)
    return tr


def _clip(evs: list[Event], lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(e.t0, lo), min(e.t1, hi)) for e in evs
            if e.t1 > lo and e.t0 < hi]


def busy_intervals(evs: list[Event], window: tuple[float, float]
                   ) -> list[tuple[float, float]]:
    """Union of the events' intervals inside ``window``, merged."""
    merged: list[list[float]] = []
    for a, b in sorted(_clip(evs, *window)):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def busy_s(tr: Trace) -> float | None:
    """Device-busy seconds in the window, averaged over the chips; None
    where the trace holds no device operation."""
    if not tr.ops:
        return None
    per_chip = [sum(b - a for a, b in busy_intervals(evs, tr.window))
                for evs in tr.ops.values()]
    return sum(per_chip) / len(per_chip) * 1e-9


def module_s(tr: Trace, pattern: str) -> tuple[float, int]:
    """Seconds and count of the window's jitted programs whose name holds
    ``pattern``, summed over the chips."""
    total, n = 0.0, 0
    for evs in tr.modules.values():
        for a, b in _clip([e for e in evs if pattern in e.name], *tr.window):
            total += b - a
            n += 1
    return total * 1e-9, n


def idle_gaps(tr: Trace) -> list[tuple[str, float]]:
    """(label, seconds) of each idle stretch of the first chip."""
    if not tr.ops:
        return []
    lo, hi = tr.window
    busy = busy_intervals(sorted(tr.ops.items())[0][1], tr.window)
    edges = [lo] + [x for ab in busy for x in ab] + [hi]
    out = []
    for a, b in zip(edges[::2], edges[1::2]):
        if b <= a:
            continue
        mid = (a + b) / 2
        around = [e for e in tr.host if e.t0 <= mid <= e.t1]
        label = (min(around, key=lambda e: e.t1 - e.t0).name if around
                 else WINDOW)
        out.append((label, (b - a) * 1e-9))
    return out


def breakdown(tr: Trace, top: int = 10) -> dict:
    """The device operations that took most time, and the idle time by
    what the host was doing, seconds each."""
    lo, hi = tr.window
    ops: dict[str, float] = defaultdict(float)
    for evs in tr.ops.values():
        for e in evs:
            if e.t1 > lo and e.t0 < hi:
                ops[e.name] += (min(e.t1, hi) - max(e.t0, lo)) * 1e-9
    idle: dict[str, float] = defaultdict(float)
    for label, s in idle_gaps(tr):
        idle[label] += s

    def rank(d: dict) -> list:
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])][:top]
    return {"device_ops": rank(ops), "idle_gaps": rank(idle)}


def idle_pct(tr: Trace | None) -> float | None:
    """Share of the window in which no operation ran on the device, %."""
    busy = busy_s(tr) if tr is not None else None
    if busy is None:
        return None
    return 100.0 * (1.0 - busy / tr.window_s)
