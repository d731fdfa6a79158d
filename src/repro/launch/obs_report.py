"""Render captured observability state: per-stage latency breakdown,
span trees, and the runtime timeline (DESIGN.md §14).

This is the consumption side of ``repro.obs`` — the artifact the online
bench embeds in BENCH_online.json and the future auto-tuner reads for
per-stage latency attribution.

Usage::

    from repro.index.registry import IndexStore
    from repro.online import OnlineRuntime, RuntimeConfig
    from repro.launch.obs_report import render_report, render_trace, report

    cfg = RuntimeConfig(semcache=True, observe=True)   # enable the seam
    rt = OnlineRuntime(db, mint, workload, constraints,
                       store=IndexStore(db, seed=0), config=cfg)
    rt.run_trace(trace)

    obs = rt.observer
    print(render_report(obs))            # human-readable breakdown+timeline
    print(render_trace(obs.traces[-1]))  # one ticket's span tree
    rep = report(obs)                    # JSON-able dict for bench artifacts
    # rep["stages"]["dispatch"]["p99"], rep["timeline"], rep["metrics"], ...

Stage rows aggregate the DIRECT children of each ticket's root span
(admission / enqueue / semcache_probe / flush_wait / dispatch / merge —
disjoint by construction, so they sum to ≈ end-to-end); ``coverage``
reports that sum over the measured total per ticket. Dispatch spans
carry the kernel-level attribution (plan signature, index kinds, batch
size, compiles) on their ``plan_group`` children, whose ``fetch``
children are the blocking reads of scan results.
"""
from __future__ import annotations

from repro.obs import Histogram, Timeline, Trace

_ATTR_KEYS = ("hit", "batch", "union", "index_kinds", "compiles")


def _fmt_attrs(attrs: dict) -> str:
    parts = [f"{key}={attrs[key]}" for key in _ATTR_KEYS if key in attrs]
    return (" [" + " ".join(parts) + "]") if parts else ""


def render_trace(trace: Trace) -> str:
    """One ticket's span tree, indented, durations in ms."""
    lines = []

    def walk(span, depth):
        lines.append(f"{'  ' * depth}{span.name:<16} "
                     f"{span.duration_ms:9.3f} ms{_fmt_attrs(span.attrs)}")
        for child in span.children:
            walk(child, depth + 1)

    walk(trace.root, 0)
    lines.append(f"stage coverage: {trace.coverage():.3f} "
                 f"(stages {trace.stage_sum_ms():.3f} ms "
                 f"of {trace.total_ms:.3f} ms)")
    return "\n".join(lines)


def stage_breakdown(traces) -> dict:
    """Aggregate top-level stages across ticket traces: per-stage count,
    mean, and p50/p95/p99 (ms), plus mean stage-sum coverage."""
    hists: dict[str, Histogram] = {}
    total = Histogram()
    coverages = []
    for trace in traces:
        for span in trace.stages():
            hists.setdefault(span.name, Histogram()).observe(span.duration_ms)
        total.observe(trace.total_ms)
        coverages.append(trace.coverage())
    out = {}
    for name, h in sorted(hists.items()):
        out[name] = {"count": h.count, "mean_ms": h.mean,
                     "p50_ms": h.quantile(0.50), "p95_ms": h.quantile(0.95),
                     "p99_ms": h.quantile(0.99)}
    return {"stages": out,
            "total": {"count": total.count, "mean_ms": total.mean,
                      "p50_ms": total.quantile(0.50),
                      "p99_ms": total.quantile(0.99)},
            "coverage_mean": (sum(coverages) / len(coverages)
                              if coverages else 0.0)}


def timeline_table(timeline: Timeline, t0: float | None = None,
                   t1: float | None = None) -> list[dict]:
    return [ev.as_dict() for ev in timeline.window(t0, t1)]


def render_timeline(timeline: Timeline, t0: float | None = None,
                    t1: float | None = None) -> str:
    evs = timeline.window(t0, t1)
    if not evs:
        return "(timeline empty)"
    base = evs[0].t
    lines = []
    for ev in evs:
        attrs = " ".join(f"{k}={v}" for k, v in ev.attrs.items())
        lines.append(f"+{(ev.t - base) * 1e3:10.3f} ms  {ev.kind:<22} {attrs}")
    return "\n".join(lines)


def report(observer) -> dict:
    """JSON-able report: stage breakdown + timeline + metrics-registry
    snapshot."""
    traces = list(observer.traces)
    return {"n_traces": len(traces),
            "breakdown": stage_breakdown(traces),
            "timeline": ([] if observer.timeline is None
                         else [ev.as_dict() for ev in observer.timeline.window()]),
            "timeline_kinds": ({} if observer.timeline is None
                               else observer.timeline.kinds()),
            "metrics": ({} if observer.metrics is None
                        else observer.metrics.snapshot().as_dict())}


def render_report(observer) -> str:
    rep = report(observer)
    lines = [f"== per-stage latency breakdown "
             f"({rep['n_traces']} ticket traces, "
             f"coverage {rep['breakdown']['coverage_mean']:.3f}) =="]
    rows = dict(rep["breakdown"]["stages"])
    rows["TOTAL"] = rep["breakdown"]["total"]
    for name, row in rows.items():
        cells = "  ".join(f"{k.replace('_ms', '')}={v:.3f}ms"
                          if isinstance(v, float) else f"{k}={v}"
                          for k, v in row.items())
        lines.append(f"  {name:<16} {cells}")
    lines.append("== runtime timeline ==")
    lines.append(render_timeline(observer.timeline)
                 if observer.timeline is not None else "(no timeline)")
    return "\n".join(lines)
