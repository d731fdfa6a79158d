"""One run of one cell: set-up, the open-loop window, the check.

Set-up (``setup_s``, process start to the first due query):

  1. the table and every query vector, made on the device from the seed
     in one call each, pulled to the host once for the program;
  2. the system under test: ``Mint`` with its planner and the empty
     index configuration, whose plans are all flat scans; then
     ``OnlineRuntime`` with the configuration's ``RuntimeConfig``;
  3. warm-up through the same entry as the window: for every vid and
     every group size B up to ``max_batch``, B warm-up queries (drawn
     from the seed, none of them in the window) submitted and drained, so
     every program the window can run is compiled before it.

Window: open loop on the wall clock. Queries are due at the mix's
arrival times; each is submitted with its due time once it is due, and
``tick`` runs the batcher's deadline. A query's latency runs from its due
time to the moment the load loop sees its ticket done. When the window's
time is up nothing more is submitted or flushed, and the flush under way
then runs to its end: ``qps`` counts every query answered by that end,
over the time from the window's start to it. Queries still queued are
served on after it (no new arrivals), and their latency counts too.

Check, once the window has closed, the peak memory is read and the
program's state is freed: every window query against the plain
reference (``bench/reference.py``).
"""
from __future__ import annotations

import contextlib
import gc
import sys
import time
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference, table, traffic
from bench import trace as btrace
from bench.spec import Cell, reader

# how long after the window's close the queries still queued may take
TAIL_S = 60.0

_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


class Meter:
    """Programs this process asked XLA for, how many of them the
    persistent cache answered, and the device's peak memory."""

    def __init__(self):
        self.requests = 0  # compiled or read back: both record a duration
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event == _COMPILE:
            self.requests += 1

    def _on_event(self, event, **_):
        if event == _CACHE_HIT:
            self.cache_hits += 1

    @staticmethod
    def peak_bytes() -> int:
        stats = [d.memory_stats() or {} for d in jax.local_devices()]
        return max(int(s.get("peak_bytes_in_use", 0)) for s in stats)


@dataclass
class Run:
    """What one run recorded; the metric readers take it."""

    cell: Cell
    device_kind: str
    seconds: float
    setup_s: float
    latency_ms: np.ndarray        # every window query, due -> done
    completed_in_window: int      # answered by the end of the last flush
    window_s: float               # start to the end of the last flush
    compiles_in_window: int
    peak_bytes: int
    resident_bytes: int
    flush_wait_ms: np.ndarray | None = None  # traced run: per window query
    dispatch_ms: np.ndarray | None = None    # traced run: per window flush
    groups: list | None = None               # traced run: (vid, B) per plan group
    trace: btrace.Trace | None = None


@dataclass
class _Clock:
    i: int = 0     # next query to submit
    head: int = 0  # first query not seen done


def _null(_name):
    return contextlib.nullcontext()


def _serve(rt, queries, due, tickets, done, clk: _Clock, until: float,
           finish: bool, ann, max_delay: float) -> None:
    """Drive the open loop until ``until`` (perf_counter seconds), or,
    with ``finish``, until every query has been answered or lost before
    then. The batcher flushes synchronously and in arrival order (one
    tenant), so a ticket flushed but not done when a call returns was
    lost: it is left at NaN in ``done``."""
    n = len(queries)
    while True:
        now = time.perf_counter()
        if now >= until:
            return
        # nothing starts after ``until``: a queue longer than one flush
        # would otherwise keep the loop flushing past it
        while clk.i < n and due[clk.i] <= now < until:
            with ann("bench.submit"):
                tickets[clk.i] = rt.submit(queries[clk.i], now=due[clk.i])
            clk.i += 1
            _collect(tickets, done, clk)  # a full batch flushes in submit
            now = time.perf_counter()
        if now >= until:
            return
        with ann("bench.tick"):
            rt.tick(now)
        _collect(tickets, done, clk)
        if finish and clk.head >= n:
            return
        nxt = min(due[clk.i] if clk.i < n else until, until)
        if clk.head < clk.i:
            nxt = min(nxt, tickets[clk.head].t_submit + max_delay)
        wait = nxt - time.perf_counter()
        if wait > 0:
            with ann("bench.wait"):
                time.sleep(wait)


def _collect(tickets, done, clk: _Clock) -> None:
    """Stamp the tickets answered since the last call."""
    t = time.perf_counter()
    while clk.head < clk.i and tickets[clk.head].flushed:
        if tickets[clk.head].done:
            done[clk.head] = t
        clk.head += 1


def _spans(tickets, w0: float, w1: float):
    """Flush waits, dispatch times and plan groups of the flushes that
    started inside the window, from the program's per-ticket spans."""
    waits, dispatches, groups = [], {}, []
    for t in tickets:
        root = t.trace.root if t.trace is not None else None
        if root is None:
            continue
        for sp in root.children:
            if sp.name == "flush_wait" and w0 <= sp.t1 <= w1:
                waits.append((sp.t1 - sp.t0) * 1e3)
            elif sp.name == "dispatch" and w0 <= sp.t0 <= w1 \
                    and sp.span_id not in dispatches:
                dispatches[sp.span_id] = (sp.t1 - sp.t0) * 1e3
                groups += [(g.attrs["plan_sig"], g.attrs["batch"])
                           for g in sp.children if g.name == "plan_group"]
    return (np.asarray(waits), np.asarray(list(dispatches.values())), groups)


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, rate: float | None = None,
             meter: Meter | None = None, warm: bool = True,
             control: bool = False) -> dict:
    """One run; returns the result line's object. ``rate`` overrides the
    mix's offered rate (the knee sweep). ``warm=False`` skips the
    warm-up and ``control=True`` adds the control's readings under
    ``"control"``: both for correctness readings only."""
    from repro.core.tuner import Mint
    from repro.core.types import Constraints, Query, TuningResult, Workload
    from repro.data.vectors import MultiVectorDatabase
    from repro.index.registry import IndexStore
    from repro.online import OnlineRuntime, RuntimeConfig

    meter = meter or Meter()
    cfg = cell.config
    vids = [tuple(v) for v in cfg["vids"]]
    k = int(cfg["k"])
    mb = int(cfg["runtime"]["max_batch"])
    max_delay = float(cfg["runtime"]["max_delay_ms"]) / 1e3
    prog_seed = int(seed) % 2 ** 31
    sched = traffic.window(cell.traffic, cfg, seed, seconds, rate)
    n = len(sched.rows)

    # 1. data, on the device, then once to the host
    t0 = time.perf_counter()
    rows = np.concatenate([sched.rows, traffic.extra_rows(
        cfg, seed, len(vids) * (mb + 1))]).astype(np.int32)
    cols = table.generate(cfg, seed)
    qdev = table.make_queries(table.query_key(seed), cols, jnp.asarray(rows),
                              noise=float(cell.traffic["query_noise"]))
    host = [np.asarray(c) for c in cols]
    qv = [np.asarray(q) for q in qdev]
    del cols, qdev
    t_data = time.perf_counter() - t0

    def query(i: int, vid) -> Query:
        return Query(qid=i, vid=vid, vectors={c: qv[c][i] for c in vid}, k=k)

    window_q = [query(i, vids[j]) for i, j in enumerate(sched.vid_index)]
    warm_q = {vid: [query(n + j * mb + b, vid) for b in range(mb)]
              for j, vid in enumerate(vids)}
    tune_q = [query(n + len(vids) * mb + j, vid) for j, vid in enumerate(vids)]

    # 2. the system under test
    t0 = time.perf_counter()
    db = MultiVectorDatabase(host, [name for name, _ in cfg["columns"]])
    workload = Workload(queries=tune_q, probs=cfg["vid_probs"])
    mint = Mint(db, index_kind=cfg["index"]["kind"], seed=prog_seed)
    cons = Constraints(theta_recall=float(cfg["theta_recall"]),
                       theta_storage=0.0)
    store = IndexStore(db, seed=prog_seed)
    planner = mint.planner(cons)
    plans = {q.qid: planner.plan(q, frozenset()) for q in tune_q}
    result = TuningResult(
        configuration=frozenset(), plans=plans, storage=0.0,
        est_workload_cost=float(sum(
            p * plans[q.qid].est_cost for q, p in workload)))
    rt = OnlineRuntime(db, mint, workload, cons, result=result, store=store,
                       config=RuntimeConfig(max_batch=mb,
                                            max_delay_ms=max_delay * 1e3,
                                            observe=trace))
    t_system = time.perf_counter() - t0

    # 3. warm-up: every (vid, group size) the window can flush
    t0 = time.perf_counter()
    if warm:
        # vids inner: the drift monitor's window then ends on a mix close
        # to the tuned one, and the window's first tick starts no retune
        for b in range(1, mb + 1):
            for vid in vids:
                now = time.perf_counter()
                for q in warm_q[vid][:b]:
                    rt.submit(q, now)
                rt.drain(now)
    t_warm = time.perf_counter() - t0
    gc.collect()
    _log(f"setup: data {t_data:.3f} s, system {t_system:.3f} s, "
         f"warm-up {t_warm:.3f} s; "
         f"compile cache: {meter.cache_hits} of {meter.requests} programs "
         f"read back")

    # window
    ann = jax.profiler.TraceAnnotation if trace else _null
    tickets = [None] * n
    done = np.full(n, np.nan)
    clk = _Clock()
    requests0 = meter.requests
    setup_s = time.perf_counter() - t_start
    w0 = time.perf_counter()
    due = w0 + sched.arrivals
    w1 = w0 + seconds
    with (btrace.capture() if trace else contextlib.nullcontext([])) as evs:
        with ann(btrace.WINDOW):
            _serve(rt, window_q, due, tickets, done, clk, w1, False, ann,
                   max_delay)
    w_end = time.perf_counter()  # the flush under way at w1 has ended
    compiles = meter.requests - requests0
    _serve(rt, window_q, due, tickets, done, clk, w1 + TAIL_S, True, _null,
           max_delay)
    tail_s = time.perf_counter() - w1
    lost = np.isnan(done)
    done[lost] = time.perf_counter()  # an answer that never came is late too
    peak = meter.peak_bytes()
    resident = rt.engine.cstore.total_device_bytes()
    latency = (done - due) * 1e3
    stats = rt.stats()
    completed = int((done <= w_end).sum())
    _log(f"window: {n} due, {int((done <= w1).sum())} done inside, "
         f"backlog {n - int((done <= w1).sum())} at close, {completed} done "
         f"by the last flush's end {w_end - w0:.6f} s, tail {tail_s:.3f} s, "
         f"{int(lost.sum())} never answered; "
         f"latency p50 {np.median(latency):.3f} ms p95 "
         f"{np.percentile(latency, 95):.3f} ms; batches "
         f"{stats['batcher']['batches']}, plan cache {stats['plan_cache']['hits']} "
         f"hits {stats['plan_cache']['misses']} misses, retunes "
         f"{stats['retunes']}, dispatches {stats['dispatches']}; "
         f"compiles in window {compiles}; peak {peak} B, resident {resident} B")
    run = Run(cell=cell, device_kind=jax.devices()[0].device_kind,
              seconds=seconds, setup_s=setup_s, latency_ms=latency,
              completed_in_window=completed, window_s=w_end - w0,
              compiles_in_window=compiles, peak_bytes=peak,
              resident_bytes=resident)
    if trace:
        run.flush_wait_ms, run.dispatch_ms, run.groups = _spans(tickets, w0,
                                                                w_end)
        run.trace = btrace.reduce(evs)

    # check, with the program's state freed
    served = [None if t is None or missing else t.ids
              for t, missing in zip(tickets, lost)]
    rt.close()
    del rt, mint, store, db, host, tickets, window_q, warm_q, result
    gc.collect()
    t0 = time.perf_counter()
    checks, failed = check(cfg, seed, vids, sched.vid_index, qv, served)
    _log(f"check: {time.perf_counter() - t0:.3f} s")
    ctl = check(cfg, seed, vids, sched.vid_index, qv, None) if control else None

    readers = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in readers:
        value = reader(m["name"], cell.root)(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    dev = jax.devices()
    device = {"platform": dev[0].platform, "kind": dev[0].device_kind,
              "count": len(dev), "memory_peak_bytes": peak}
    out = {"correct": bool(failed == 0 and all(within(c) for c
                                              in checks.values())),
           "attempted": n, "failed": int(failed), "metrics": metrics,
           "device": device}
    if trace and run.trace is not None:
        busy = btrace.busy_s(run.trace)
        if busy is not None:
            device.update(busy_s=busy, window_s=run.trace.window_s)
            out["breakdown"] = btrace.breakdown(run.trace)
    if ctl is not None:
        out["control"] = {"checks": ctl[0], "failed": ctl[1]}
    out["checks"] = checks
    return out


def within(c: dict) -> bool:
    """A compared number passes at or below its limit; a missing one
    fails."""
    return c["value"] is not None and c["value"] <= c["limit"]


def check(cfg: dict, seed: int, vids, vid_index, qv,
          served: list | None) -> tuple[dict, int]:
    """Compare ``served`` (ids or None per window query) with the plain
    reference on the regenerated table; ``served`` None compares the
    control instead, the reference at bf16x3 in the program's place.
    Returns the compared numbers, each with its limit, and how many
    queries failed."""
    k, n_rows = int(cfg["k"]), int(cfg["rows"])
    cols = table.generate(cfg, seed)
    n = len(vid_index)
    good = np.zeros(n, dtype=bool)
    gap = np.full(n, np.inf)
    for j, vid in enumerate(vids):
        idx = np.nonzero(np.asarray(vid_index) == j)[0]
        if not idx.size:
            continue
        qvecs = [qv[c][idx] for c in vid]
        got = (list(reference.best_rows(cols, vid, qvecs, k, "bf16x3"))
               if served is None else [served[i] for i in idx])
        r = reference.judge(cols, vid, qvecs, got, k, n_rows)
        good[idx], gap[idx] = r["ok"], r["gap"]
    del cols
    limits = cfg["checks"]
    values = {"bad_answers": int((~good).sum()),
              "score_gap": float(gap[good].max()) if good.any() else None}
    checks = {name: {"value": values[name], "limit": limits[name]}
              for name in limits}
    bad = ~good
    if "score_gap" in limits:
        bad |= gap > limits["score_gap"]
    return checks, int(bad.sum())
