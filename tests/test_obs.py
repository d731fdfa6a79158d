"""Unified observability layer (DESIGN.md §14).

Three layers of guarantees:

  - primitives: log-bucketed histogram exactness at bucket boundaries,
    registry thread-safety under real WorkerPool contention, label-
    cardinality bounding, snapshot diff/merge round-trips, exporters;
  - per-ticket tracing: the sync and async serving paths both yield a
    COMPLETE stage set (enqueue / semcache_probe / flush_wait / dispatch
    / merge, after an ``admission`` stage where the caller's ``now`` is
    an arrival on the runtime clock) whose top-level stages are disjoint
    and sum to ≈ end-to-end latency; async flush spans built on worker
    threads are adopted into every served ticket's root; plan groups and
    the fetches of their results nest under dispatch, in the span tree
    and in a profiler capture; compiles are charged to the live span
    that caused them;
  - zero-cost-when-disabled: observer-off runs produce bit-identical
    results through the NULL_OBSERVER seam, and seeded StepExecutor
    interleavings reproduce identical span trees and counters.
"""
import json
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.async_ import SerialExecutor, StepExecutor, WorkerPool
from repro.autotune.replay import _drive
from repro.core.tuner import Mint
from repro.core.types import Constraints, Workload
from repro.data.vectors import make_database, make_queries
from repro.index.registry import IndexStore
from repro.obs import (COUNTER, GAUGE, HISTOGRAM, NULL_OBSERVER, Histogram,
                       MetricsRegistry, MetricsSnapshot, Observer, Timeline,
                       hist_quantile, hist_summary)
from repro.online import OnlineRuntime, RuntimeConfig, hot_item_trace
from repro.obs import observer as observer_mod
from repro.online.semcache import SemanticCache

K = 8
COLS = [("a", 24), ("b", 32)]
STAGES = {"enqueue", "semcache_probe", "flush_wait", "dispatch", "merge"}


@pytest.fixture(scope="module")
def db():
    return make_database(400, COLS, seed=0)


@pytest.fixture(scope="module")
def wl(db):
    qs = make_queries(db, [(0,), (0, 1), (1,)], k=K, seed=7)
    return Workload(queries=qs, probs=np.ones(len(qs)))


@pytest.fixture(scope="module")
def cons():
    return Constraints(theta_recall=0.85, theta_storage=3)


@pytest.fixture(scope="module")
def mint(db):
    return Mint(db, index_kind="ivf", seed=0, min_sample_rows=300)


@pytest.fixture(scope="module")
def tuned(mint, wl, cons):
    return mint.tune(wl, cons)


@pytest.fixture(scope="module")
def trace(db):
    return hot_item_trace(db, vid=(0,), n=48, qps=2000.0, n_hot=3,
                          p_hot=0.8, k=K, seed=7, noise=0.1,
                          qid_start=90_000)


def _runtime(db, mint, wl, cons, tuned, executor=None, **kw):
    return OnlineRuntime(db, mint, wl, cons, result=tuned,
                         store=IndexStore(db, seed=0), executor=executor,
                         config=RuntimeConfig(**kw))


# ---- histogram primitives --------------------------------------------------


def test_histogram_bucket_boundaries_are_exact():
    """Upper-inclusive geometric buckets: a value EQUAL to a bound lands
    in that bound's bucket (bisect_left, no float-log fuzz), and the
    quantile of a boundary-only population reproduces the bounds."""
    h = Histogram(lo=1.0, growth=2.0, n_buckets=8)
    assert h.bounds == [1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0, 128.0]
    for v in h.bounds:
        h.observe(v)
    assert h.counts == [1] * 8 and h.overflow == 0
    # rank-q over the 8 boundary values is the boundary itself, exactly
    assert h.quantile(0.5) == 8.0
    assert h.quantile(1.0) == 128.0
    assert h.quantile(1 / 8) == 1.0
    # below-lo clamps into bucket 0; above-top goes to overflow but the
    # quantile stays capped at the exact observed max
    h2 = Histogram(lo=1.0, growth=2.0, n_buckets=4)
    h2.observe(0.01)
    assert h2.counts[0] == 1
    h2.observe(1e9)
    assert h2.overflow == 1
    assert h2.quantile(0.99) == 1e9 == h2.vmax


def test_histogram_quantile_relative_error_and_merge():
    h = Histogram()  # defaults: growth 2**0.25 => <= ~19% relative error
    vals = np.linspace(0.5, 400.0, 1000)
    for v in vals:
        h.observe(float(v))
    for q in (0.5, 0.95, 0.99):
        exact = float(np.quantile(vals, q))
        assert abs(h.quantile(q) - exact) / exact < 0.2
    assert abs(h.mean - float(np.mean(vals))) < 1e-6
    a, b = Histogram(), Histogram()
    for v in vals[:500]:
        a.observe(float(v))
    for v in vals[500:]:
        b.observe(float(v))
    a.merge(b)
    assert a.count == h.count and a.counts == h.counts
    assert a.quantile(0.99) == h.quantile(0.99)
    with pytest.raises(ValueError):
        a.merge(Histogram(lo=1.0, growth=2.0, n_buckets=4))


def test_hist_data_roundtrip_and_summary():
    h = Histogram()
    for v in (0.5, 2.0, 7.5, 300.0):
        h.observe(v)
    d = json.loads(json.dumps(h.data()))  # survives JSON
    assert hist_quantile(d, 0.99) == h.quantile(0.99)
    s = hist_summary(d)
    assert s["count"] == 4 and s["min"] == 0.5 and s["max"] == 300.0
    assert set(s) == {"count", "mean", "min", "max", "p50", "p95", "p99"}


# ---- registry --------------------------------------------------------------


def test_registry_kinds_labels_and_snapshot():
    reg = MetricsRegistry()
    reg.counter("hits", tenant="a")
    reg.counter("hits", value=2, tenant="a")
    reg.counter("hits", tenant="b")
    reg.gauge("depth", 3.5)
    reg.observe("wait_ms", 12.0, tenant="a")
    snap = reg.snapshot()
    assert snap.get("hits", tenant="a")["value"] == 3
    assert snap.get("hits", tenant="b")["value"] == 1
    assert snap.get("depth")["kind"] == GAUGE
    assert snap.get("wait_ms", tenant="a")["kind"] == HISTOGRAM
    assert snap.get("wait_ms", tenant="a")["data"]["count"] == 1
    # snapshot is a copy: later updates don't leak into it
    reg.counter("hits", tenant="a")
    assert snap.get("hits", tenant="a")["value"] == 3
    reg.reset()
    assert not reg.snapshot().series


def test_label_cardinality_bound_routes_to_overflow():
    reg = MetricsRegistry(max_series_per_name=3)
    for i in range(10):
        reg.counter("q", qid=i)
    snap = reg.snapshot()
    keys = [k for k in snap.series if k[0] == "q"]
    assert len(keys) == 4  # 3 real label sets + the overflow series
    assert snap.get("q", overflow="true")["value"] == 7
    assert snap.dropped_labelsets == {"q": 7}
    # other metric names are unaffected by q's overflow
    reg.counter("ok", tenant="t")
    assert reg.snapshot().get("ok", tenant="t")["value"] == 1


def test_snapshot_diff_merge_roundtrip():
    reg = MetricsRegistry()
    reg.counter("c", tenant="a")
    reg.observe("h", 1.0)
    s0 = reg.snapshot()
    reg.counter("c", value=4, tenant="a")
    reg.gauge("g", 9.0)
    for v in (2.0, 8.0):
        reg.observe("h", v)
    s1 = reg.snapshot()
    d = s1.diff(s0)
    assert d.get("c", tenant="a")["value"] == 4
    assert d.get("g")["value"] == 9.0
    assert d.get("h")["data"]["count"] == 2
    # older + diff == newer (counters and histogram counts; gauges take
    # the newer value by definition)
    back = s0.merge(d)
    assert back.get("c", tenant="a") == s1.get("c", tenant="a")
    assert back.get("g") == s1.get("g")
    assert back.get("h")["data"]["counts"] == s1.get("h")["data"]["counts"]
    assert back.get("h")["data"]["count"] == 3
    # self-diff: counters and histograms vanish; gauges carry through
    # (they take the newer value by definition, not a delta)
    self_diff = s1.diff(s1)
    assert set(self_diff.series) == {("g", ())}


def test_exporters_parse():
    reg = MetricsRegistry()
    reg.counter("hits", tenant="a")
    reg.observe("wait_ms", 3.0, tenant="a")
    snap = reg.snapshot()
    for line in snap.to_jsonl().splitlines():
        rec = json.loads(line)
        assert rec["kind"] in (COUNTER, GAUGE, HISTOGRAM)
    prom = snap.to_prometheus()
    assert "# TYPE hits counter" in prom
    assert "# TYPE wait_ms histogram" in prom
    assert 'wait_ms_bucket{tenant="a",le="+Inf"} 1' in prom
    d = snap.as_dict()
    assert d["hits{tenant=a}"] == 1 and d["wait_ms{tenant=a}"]["count"] == 1
    json.dumps(d)  # JSON-able end to end


def test_registry_concurrent_updates_from_worker_pool():
    """The single-RLock registry must not lose updates under real thread
    contention: N workers hammer one counter and one histogram series."""
    reg = MetricsRegistry()
    n_tasks, per_task = 16, 500

    def work(i):
        for j in range(per_task):
            reg.counter("c", tenant="shared")
            reg.observe("h", float(j % 7), tenant="shared")

    with WorkerPool(workers=4, name="obs-t") as pool:
        futs = [pool.submit(work, i, label=f"w:{i}") for i in range(n_tasks)]
        for f in futs:
            f.result(timeout=30)
    snap = reg.snapshot()
    assert snap.get("c", tenant="shared")["value"] == n_tasks * per_task
    assert snap.get("h", tenant="shared")["data"]["count"] == n_tasks * per_task


# ---- observer + spans + timeline -------------------------------------------


def test_span_nesting_follows_thread_local_stack():
    obs = Observer()
    with obs.span("outer") as outer:
        assert obs.current() is outer
        with obs.span("inner", depth=2) as inner:
            assert obs.current() is inner
        sp = obs.span_at("retro", 1.0, 2.0, parent=obs.current())
    assert obs.current() is None
    assert [c.name for c in outer.children] == ["inner", "retro"]
    assert sp.duration_ms == pytest.approx(1000.0)
    assert outer.t1 is not None  # context exit closed it
    # stacks are PER-THREAD: a worker thread sees no parent
    seen = []
    t = threading.Thread(target=lambda: seen.append(obs.current()))
    with obs.span("main-only"):
        t.start()
        t.join()
    assert seen == [None]


def test_null_observer_absorbs_everything():
    obs = NULL_OBSERVER
    assert not obs.enabled and obs.traces == ()
    assert obs.begin_trace("t") is None
    with obs.span("x") as sp:
        sp.annotate(a=1).end()
        sp.add(object())
    obs.counter("c")
    obs.observe("h", 1.0)
    obs.event("e", foo="bar")
    assert obs.span_at("y", 0.0, 1.0).duration_ms == 0.0


def test_timeline_window_kinds_and_bound():
    tl = Timeline(capacity=4)
    for i in range(6):
        tl.record("swap" if i % 2 else "evict", t=float(i), gen=i)
    assert len(tl) == 4  # bounded ring: oldest two dropped
    assert [e.t for e in tl.window()] == [2.0, 3.0, 4.0, 5.0]
    assert [e.t for e in tl.window(t0=3.0, t1=4.5)] == [3.0, 4.0]
    assert [e.t for e in tl.window(kind="swap")] == [3.0, 5.0]
    assert tl.kinds() == {"swap": 2, "evict": 2}
    assert tl.window()[0].as_dict() == {"t": 2.0, "kind": "evict",
                                        "attrs": {"gen": 2}}


def test_observer_event_feeds_timeline_and_counter():
    obs = Observer()
    obs.event("retune_swap", generation=3)
    obs.event("retune_swap", generation=4)
    assert obs.timeline.kinds() == {"retune_swap": 2}
    snap = obs.metrics.snapshot()
    assert snap.get("events", kind="retune_swap")["value"] == 2


def test_semcache_bump_emits_invalidate_event():
    obs = Observer()
    sc = SemanticCache(observer=obs)
    sc.bump()
    evs = obs.timeline.window(kind="semcache_invalidate")
    assert len(evs) == 1 and evs[0].attrs["epoch"] == 1


def test_executor_task_metrics_bound_kind_cardinality():
    obs = Observer()
    ex = SerialExecutor(observer=obs)
    for label in ("flush:size", "flush:deadline", "retune@12.5", "build"):
        ex.submit(lambda: None, label=label).result(timeout=1)
    snap = obs.metrics.snapshot()
    # label suffixes (reason, timestamp) are stripped to a bounded kind
    assert snap.get("executor_tasks", kind="flush")["value"] == 2
    assert snap.get("executor_tasks", kind="retune")["value"] == 1
    assert snap.get("executor_tasks", kind="build")["value"] == 1
    assert snap.get("executor_task_ms", kind="flush")["data"]["count"] == 2


# ---- per-ticket tracing through the serving stack --------------------------


def _complete_traces(obs):
    return [tr for tr in obs.traces if STAGES <= tr.stage_names()]


def test_sync_ticket_span_tree_is_complete_and_disjoint(db, mint, wl, cons,
                                                        tuned, trace):
    rt = _runtime(db, mint, wl, cons, tuned, max_batch=4, max_delay_ms=5.0,
                  cooldown_s=1e9, drift_threshold=2.0, semcache=True,
                  semcache_epsilon=0.1, observe=True)
    tickets = rt.run_trace(trace)
    assert all(t.done for t in tickets)
    full = _complete_traces(rt.observer)
    assert full, "no ticket produced a complete span tree"
    for tr in full:
        # top-level stages are disjoint by construction -> their sum
        # accounts for ≈ the whole end-to-end latency (±10% acceptance)
        assert 0.9 <= tr.coverage() <= 1.1
        dsp = tr.find("dispatch")
        # kernel-level attribution rides on dispatch: plan groups nested
        # via the thread's live-span stack, each holding the fetch of its
        # scan results
        groups = [s for s in dsp.walk() if s.name == "plan_group"]
        assert groups
        for g in groups:
            assert any(c.name == "fetch" for c in g.children)
            assert g.attrs["plan_sig"] and g.attrs["batch"] >= 1
        # virtual time: the trace opens at the submit, no admission stage
        assert "admission" not in tr.stage_names()
        # plan_cache nests INSIDE enqueue (top-level stays disjoint)
        enq = tr.find("enqueue")
        assert all(c.name == "plan_cache" for c in enq.children)
    # cache-hit tickets complete at submit: enqueue + probe only, no
    # dispatch — and the registry saw them as semcache_hits
    snap = rt.observer.metrics.snapshot()
    hits = snap.get("semcache_hits", tenant="")
    hit_traces = [tr for tr in rt.observer.traces
                  if "dispatch" not in tr.stage_names()]
    if hits:
        assert len(hit_traces) == hits["value"]
    assert snap.get("tickets_submitted", tenant="")["value"] == len(trace)
    wall = snap.get("ticket_wall_ms", tenant="")
    assert wall["data"]["count"] == len(trace)
    rt.close()


def test_async_flush_spans_adopt_into_ticket_roots(db, mint, wl, cons,
                                                   tuned, trace):
    """Across the WorkerPool boundary: the dispatch/merge spans are built
    on the worker thread and adopted BY REFERENCE into every served
    ticket's root; flush_wait covers enqueue -> worker pickup."""
    rt = _runtime(db, mint, wl, cons, tuned,
                  executor=StepExecutor(seed=0), max_batch=4,
                  max_delay_ms=5.0, cooldown_s=1e9, drift_threshold=2.0,
                  async_flush=True, semcache=True, semcache_epsilon=0.1,
                  observe=True)
    tickets = rt.run_trace(trace)
    ids = [np.asarray(t.result(timeout=30)) for t in tickets]
    assert all(len(i) for i in ids)
    full = _complete_traces(rt.observer)
    assert full
    # tickets flushed in the same batch SHARE the dispatch span object
    by_dispatch = {}
    for tr in full:
        by_dispatch.setdefault(id(tr.find("dispatch")), []).append(tr)
    # every miss ticket traces, so traced flushes == recorded flushes
    batch = rt.observer.metrics.snapshot().get("flush_batch")
    assert batch["data"]["count"] == len(by_dispatch) >= 1
    for trs in by_dispatch.values():
        sizes = {tr.find("dispatch").attrs["batch"] for tr in trs}
        assert len(sizes) == 1 and sizes.pop() >= len(trs)
    for tr in full:
        assert 0.9 <= tr.coverage() <= 1.1
        assert tr.find("dispatch").find("fetch") is not None
    snap = rt.observer.metrics.snapshot()
    assert snap.get("executor_tasks", kind="flush")["value"] >= 1
    rt.close()


def test_seeded_interleavings_reproduce_span_trees_and_counters(
        db, mint, wl, cons, tuned, trace):
    def run(seed):
        rt = _runtime(db, mint, wl, cons, tuned,
                      executor=StepExecutor(seed=seed), max_batch=4,
                      max_delay_ms=5.0, cooldown_s=1e9, drift_threshold=2.0,
                      async_flush=True, semcache=True, semcache_epsilon=0.1,
                      observe=True)
        tickets = rt.run_trace(trace)
        ids = [np.asarray(t.result(timeout=30)) for t in tickets]
        # structure, not timing: per-ticket stage multiset + batch sizes
        shapes = [(sorted(tr.stage_names()),
                   tr.find("dispatch").attrs.get("batch")
                   if tr.find("dispatch") else None)
                  for tr in rt.observer.traces]
        snap = rt.observer.metrics.snapshot()
        # compiles depend on what this process compiled before, not on
        # the interleaving
        counters = {k: v["value"] for k, v in snap.series.items()
                    if v["kind"] == COUNTER and k[0] != "compiles"}
        hcounts = {k: v["data"]["count"] for k, v in snap.series.items()
                   if v["kind"] == HISTOGRAM}
        rt.close()
        return ids, shapes, counters, hcounts

    ids0, shapes0, counters0, hcounts0 = run(3)
    ids1, shapes1, counters1, hcounts1 = run(3)
    for a, b in zip(ids0, ids1):
        np.testing.assert_array_equal(a, b)
    assert shapes0 == shapes1
    assert counters0 == counters1 and hcounts0 == hcounts1


def test_observer_disabled_is_bit_identical_and_inert(db, mint, wl, cons,
                                                      tuned, trace):
    def run(observe):
        rt = _runtime(db, mint, wl, cons, tuned, max_batch=4,
                      max_delay_ms=5.0, cooldown_s=1e9, drift_threshold=2.0,
                      semcache=True, semcache_epsilon=0.1, observe=observe)
        tickets = rt.run_trace(trace)
        ids = [np.asarray(t.result(timeout=30)) for t in tickets]
        obs = rt.observer
        rt.close()
        return ids, obs

    ids_off, obs_off = run(False)
    ids_on, obs_on = run(True)
    for a, b in zip(ids_off, ids_on):
        np.testing.assert_array_equal(a, b)
    # disabled mode is the NULL seam: no state anywhere, and the runtime
    # surfaces no metrics section
    assert obs_off is NULL_OBSERVER and not obs_off.traces
    assert obs_on.traces


def test_runtime_stats_surface_metrics_and_snapshot_semantics(
        db, mint, wl, cons, tuned, trace):
    rt = _runtime(db, mint, wl, cons, tuned, max_batch=4, max_delay_ms=5.0,
                  cooldown_s=1e9, drift_threshold=2.0, semcache=True,
                  semcache_epsilon=0.1, observe=True)
    rt.run_trace(trace)
    st = rt.stats()
    assert "metrics" in st
    assert st["metrics"]["tickets_submitted{tenant=}"] == len(trace)
    assert st["metrics"]["ticket_wall_ms{tenant=}"]["count"] == len(trace)
    # snapshot_stats is read-only: two reads agree, live object untouched
    s1 = rt.batcher.snapshot_stats()
    s2 = rt.batcher.snapshot_stats()
    assert vars(s1) == vars(s2)
    assert rt.batcher.stats.batches == s1.batches
    pre = rt.batcher.reset_stats()  # explicit reset returns the final view
    assert pre.batches == s1.batches
    assert rt.batcher.stats.batches == 0
    rt.close()


def test_snapshot_diff_clamps_counter_resets():
    """A registry reset between snapshots must not yield negative
    deltas: the diff clamps at the post-reset value and carries an
    explicit ``resets`` marker instead."""
    reg = MetricsRegistry()
    reg.counter("c", value=10, tenant="a")
    reg.observe("h", 5.0)
    s0 = reg.snapshot()
    reg.reset()
    reg.counter("c", value=3, tenant="a")
    reg.observe("h", 1.0)
    s1 = reg.snapshot()
    d = s1.diff(s0)
    entry = d.get("c", tenant="a")
    assert entry["value"] == 3          # post-reset value, not 3 - 10
    assert entry["resets"] == 1
    h = d.get("h")
    assert h["data"]["count"] == 1      # the post-reset window verbatim
    assert h["data"]["total"] == 1.0
    assert h["resets"] == 1
    assert d.resets == {"c": 1, "h": 1}
    assert d.as_dict()["_resets"] == {"c": 1, "h": 1}
    # merge(base, clamped-diff) stays sane: counters never go negative
    back = s0.merge(d)
    assert back.get("c", tenant="a")["value"] == 13
    # a clean diff carries no reset markers
    clean = s1.diff(s1)
    assert clean.resets == {} and "_resets" not in clean.as_dict()


# ---- arrival, the profiler bridge, compiles by span ------------------------


def _stages_disjoint(tr) -> bool:
    stages = sorted(tr.stages(), key=lambda sp: sp.t0)
    return all(a.t1 <= b.t0 for a, b in zip(stages, stages[1:]))


@pytest.mark.parametrize("clock", ["runtime", "wall"])
def test_admission_span_opens_the_trace_at_the_arrival(db, mint, wl, cons,
                                                       tuned, clock):
    """A ``now`` on the runtime clock is the arrival: the root opens
    there and an ``admission`` stage covers arrival -> submit, disjoint
    from the rest. A ``now`` from another clock (here the wall clock,
    ahead of ``perf_counter``) is no arrival: the trace opens at the
    submit."""
    rt = _runtime(db, mint, wl, cons, tuned, max_batch=4, max_delay_ms=5.0,
                  cooldown_s=1e9, drift_threshold=2.0, semcache=True,
                  observe=True)
    queries = [q for q, _ in wl][:3]
    arrivals = []
    for q in queries:
        arrivals.append(time.perf_counter() - 0.05 if clock == "runtime"
                        else time.time())
        rt.submit(q, now=arrivals[-1])
    rt.drain()
    traces = list(rt.observer.traces)
    assert len(traces) == len(queries)
    for t_arr, tr in zip(arrivals, traces):
        assert STAGES <= tr.stage_names()
        assert _stages_disjoint(tr)
        assert 0.9 <= tr.coverage() <= 1.1
        adm = tr.find("admission")
        if clock == "runtime":
            assert tr.root.t0 == t_arr == adm.t0
            assert adm.duration_ms >= 50.0
            assert adm.t1 == tr.find("enqueue").t0
        else:
            assert adm is None and tr.root.t0 == tr.find("enqueue").t0
    rt.close()


@pytest.mark.parametrize("replay", ["run_trace", "_drive"])
def test_virtual_time_replays_build_no_admission_span(db, mint, wl, cons,
                                                      tuned, trace, replay):
    """Trace time starts at 0, before any ``perf_counter`` reading: the
    virtual-time replays mark themselves, so it is never read as an
    arrival."""
    rt = _runtime(db, mint, wl, cons, tuned, executor=StepExecutor(seed=0),
                  max_batch=4, max_delay_ms=5.0, cooldown_s=1e9,
                  drift_threshold=2.0, observe=True)
    if replay == "run_trace":
        rt.run_trace(trace)
    else:
        _drive(rt, trace, rt.executor)
    traces = list(rt.observer.traces)
    assert len(traces) == len(trace)
    for tr in traces:
        assert "admission" not in tr.stage_names()
        assert tr.root.t0 == tr.find("enqueue").t0
        assert 0.9 <= tr.coverage() <= 1.1
    rt.close()


def test_live_spans_nest_in_the_profiler_trace(db, mint, wl, cons, tuned,
                                               tmp_path):
    """A profiler capture of an observed flush holds, on the flushing
    thread's host line, mint.dispatch ⊃ mint.plan_group ⊃ mint.fetch."""
    rt = _runtime(db, mint, wl, cons, tuned, max_batch=4, max_delay_ms=5.0,
                  cooldown_s=1e9, drift_threshold=2.0, observe=True)
    queries = [q for q, _ in wl][:3]
    for q in queries:  # warm: compile outside the capture
        rt.submit(q)
    rt.drain()
    jax.profiler.start_trace(str(tmp_path))
    try:
        for q in queries:
            rt.submit(q)
        rt.drain()
    finally:
        jax.profiler.stop_trace()
    rt.close()
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(sorted(tmp_path.rglob("*.xplane.pb"))[-1]))
    found = False
    for plane in pd.planes:
        for line in plane.lines:
            evs = {}
            for ev in line.events:
                if ev.name.startswith("mint."):
                    evs.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns))
            if "mint.dispatch" not in evs:
                continue

            def inside(inner, outer):
                return any(o0 <= i0 and i1 <= o1
                           for i0, i1 in evs[inner] for o0, o1 in evs[outer])

            assert inside("mint.plan_group", "mint.dispatch")
            assert inside("mint.fetch", "mint.plan_group")
            found = True
    assert found


def test_compiles_are_charged_to_the_live_span_that_made_them(db, mint, wl,
                                                              cons, tuned):
    """A batch shape XLA has not compiled charges its compiles to the
    plan group that asked for them, on the span and in the registry; a
    shape already compiled charges nothing, nor does a compile with no
    live span open."""
    rt = _runtime(db, mint, wl, cons, tuned, max_batch=8, max_delay_ms=5.0,
                  cooldown_s=1e9, drift_threshold=2.0, observe=True)
    obs = rt.observer
    queries = [q for q, _ in wl if q.vid == (0,)]

    def flush(b):
        for q in (queries * 8)[:b]:
            rt.submit(q)
        rt.drain()
        return obs.traces[-1].find("plan_group")

    def charged():
        entry = obs.metrics.snapshot().get("compiles", span="plan_group")
        return entry["value"] if entry else 0

    jax.clear_caches()
    first = flush(2)
    n0 = charged()
    assert first.attrs["compiles"] >= 1 and n0 >= first.attrs["compiles"]
    again = flush(2)
    assert "compiles" not in again.attrs and charged() == n0
    new_shape = flush(5)
    assert new_shape.attrs["compiles"] >= 1
    assert charged() == n0 + new_shape.attrs["compiles"]
    assert not any(k[0] == "compiles" and k[1] != (("span", "plan_group"),)
                   for k in obs.metrics.snapshot().series)
    jax.jit(lambda x: x * 3.0 + 1.0)(jnp.ones(7))  # no live span open
    assert charged() == n0 + new_shape.attrs["compiles"]
    rt.close()


def test_disabled_observer_registers_no_listener_and_enters_no_annotation(
        db, mint, wl, cons, tuned, trace, monkeypatch):
    """The NULL seam adds no compile listener and no profiler annotation;
    enabled observers register one listener per process, however many
    there are, and annotate each live span as ``mint.<name>``."""
    registered, entered = [], []

    class Annotation:
        def __init__(self, name):
            entered.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(observer_mod, "_listening", False)
    monkeypatch.setattr(jax.monitoring,
                        "register_event_duration_secs_listener",
                        registered.append)
    monkeypatch.setattr(observer_mod, "TraceAnnotation", Annotation)
    rt = _runtime(db, mint, wl, cons, tuned, max_batch=4, max_delay_ms=5.0,
                  cooldown_s=1e9, drift_threshold=2.0, observe=False)
    rt.run_trace(trace)
    rt.close()
    assert registered == [] and entered == []
    obs = Observer()
    Observer()
    assert registered == [observer_mod._on_duration]
    with obs.span("dispatch"):
        with obs.span("plan_group"):
            obs.span_at("rerank", 0.0, 1.0, parent=obs.current())
    assert entered == ["mint.dispatch", "mint.plan_group"]
