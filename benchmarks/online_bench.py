"""Online serving runtime under drift: background re-tuning vs stale plans.

Tunes on a "day" workload (columns 0/1), then serves a steady day segment
followed by a diurnal drift into a "night" workload (columns 2/3). Two
runtimes serve the identical trace:

  - stale   : drift detection disabled — the day configuration and its
              plan-cache templates serve the night traffic (unseen vids
              degrade to flat scans);
  - retuned : the drift detector fires mid-drift, the background re-tuner
              re-runs Mint.tune on the observed window, shadow-builds the
              night configuration, and atomically swaps it in.

Reports, on the drifted evaluation window: mean executed cost (the paper's
dim-weighted distance proxy), mean recall vs theta_recall (mean AND the
fraction of individual queries below theta), and amortized execution wall
time — plus the plan-cache hit rate on the steady segment, a
burst-scenario micro-batching summary, and the semantic-result-cache
ε-sweep (hit rate vs measured recall, p99 with/without the cache).
Emits BENCH_online.json.

    PYTHONPATH=src python benchmarks/online_bench.py [--rows 10000]
"""
import argparse
import json
import time

import numpy as np

from repro.core.types import Constraints, Workload
from repro.core.tuner import Mint
from repro.data.vectors import make_database, make_queries
from repro.index.base import exact_topk
from repro.index.registry import IndexStore
from repro.launch.obs_report import report as obs_report
from repro.obs import Histogram
from repro.online import (OnlineRuntime, RuntimeConfig, burst_trace,
                          diurnal_trace, hot_item_trace, steady_trace,
                          tenant_skew_trace)
from repro.tenancy import MultiTenantRuntime, Tenant
from repro.launch.entry import start


def vid_workload(db, vids, k, seed):
    qs = make_queries(db, vids, k=k, seed=seed)
    return Workload(queries=qs, probs=np.ones(len(qs)))


def window_metrics(tickets, theta_recall) -> dict:
    ms = [t.metrics for t in tickets]
    recalls = np.asarray([m.recall for m in ms])
    # end-to-end wall wait (submit -> result ready) through the obs
    # histogram: log-bucketed, so p50/p99 match what the metrics registry
    # reports for ticket_wall_ms in observer-enabled runs
    waits = Histogram()
    for t in tickets:
        waits.observe(max(t.wall_wait_ms, 0.0))
    return {
        "mean_wall_wait_ms": waits.mean,
        "p50_wall_wait_ms": waits.quantile(0.50),
        "p99_wall_wait_ms": waits.quantile(0.99),
        "queries": len(ms),
        "mean_cost": float(np.mean([m.cost for m in ms])),
        "p50_cost": float(np.percentile([m.cost for m in ms], 50)),
        "mean_recall": float(np.mean(recalls)),
        "min_recall": float(np.min(recalls)),
        "theta_recall_met": bool(np.mean(recalls) >= theta_recall),
        # mean recall can clear theta while a tail of individual queries
        # does not — report that floor alongside the mean, don't hide it
        "frac_below_theta": float(np.mean(recalls < theta_recall)),
        "mean_exec_wall_ms": float(np.mean([m.wall_ms for m in ms])),
    }


def run_variant(db, mint, day, cons, result, store, steady, drifted,
                retune: bool) -> dict:
    cfg = RuntimeConfig(max_batch=16, max_delay_ms=5.0, window=96,
                        min_window=48, cooldown_s=0.02, measure=True,
                        drift_threshold=0.35 if retune else 2.0)
    rt = OnlineRuntime(db, mint, day, cons, result=result, store=store,
                       config=cfg)
    rt.run_trace(steady)
    steady_cache = rt.cache.stats()
    rt.cache.reset_counters()
    tickets = rt.run_trace(drifted)
    n_eval = len(drifted) // 3  # night-dominated tail of the diurnal shift
    out = {
        "steady_plan_cache": steady_cache,
        "drift_tail": window_metrics(tickets[-n_eval:], cons.theta_recall),
        "batcher": rt.batcher.snapshot_stats().as_dict(),
        "retunes": [vars(e) for e in rt.retune_events],
        "generation": rt.generation,
        "serving_config": sorted(s.name for s in rt.result.configuration),
        "store_size": len(rt.store.built_specs()),
    }
    return out


def burst_summary(db, mint, day, cons, result, store) -> dict:
    """Modality burst: the micro-batcher should amortize the burst into
    few, large plan groups (dispatch counts vs query count)."""
    cfg = RuntimeConfig(max_batch=16, max_delay_ms=5.0, window=96,
                        min_window=48, cooldown_s=1e9, drift_threshold=2.0)
    rt = OnlineRuntime(db, mint, day, cons, result=result, store=store,
                       config=cfg)
    trace = burst_trace(db, day, burst_vid=(0, 1), n=160, qps=2000.0,
                        seed=11, qid_start=50_000)
    rt.run_trace(trace)
    st = rt.stats()
    return {"queries": len(trace), "batches": st["batcher"]["batches"],
            "mean_batch": st["batcher"]["mean_batch"],
            "scan_dispatches": st["dispatches"]["scan"],
            "plan_cache_hit_rate": st["plan_cache"]["hit_rate"]}


def async_flush_overlap(db, mint, day, cons, result) -> dict:
    """Flush-pipeline overlap (DESIGN.md §10): the same burst served with
    in-line flushes vs the worker pool (batch N+1's host→device staging
    overlaps batch N's kernel dispatch). Virtual-time trace, wall-clock
    processing: the wall ratio is the pipeline gain; ids are checked
    bit-identical between the two modes."""
    from repro.online import burst_trace

    trace = burst_trace(db, day, burst_vid=(0, 1), n=240, qps=4000.0,
                        seed=23, qid_start=80_000)
    out = {}
    ids = {}
    # a throwaway FULL run first: whichever runtime goes first otherwise
    # pays ~5s of process-wide warm-up (index-build jit, kernel compiles)
    # that the per-runtime warm below does not cover, which once inflated
    # the "overlap speedup" of whatever mode happened to run second
    warm = OnlineRuntime(db, mint, day, cons, result=result,
                         store=IndexStore(db, seed=0),
                         config=RuntimeConfig(max_batch=16, cooldown_s=1e9,
                                              drift_threshold=2.0))
    warm.run_trace(trace)
    for mode in ("sync", "async"):
        cfg = RuntimeConfig(max_batch=16, max_delay_ms=5.0, window=96,
                            min_window=48, cooldown_s=1e9,
                            drift_threshold=2.0,
                            async_flush=(mode == "async"), workers=2)
        rt = OnlineRuntime(db, mint, day, cons, result=result,
                           store=IndexStore(db, seed=0), config=cfg)
        rt.run_trace(trace[:32])  # warm kernels + plan cache
        t0 = time.time()
        tickets = rt.run_trace(trace)
        wall = time.time() - t0
        ids[mode] = [np.asarray(t.result(timeout=60)) for t in tickets]
        st = rt.batcher.snapshot_stats()
        out[mode] = {
            "wall_s": float(wall),
            "queries_per_s": float(len(tickets) / max(wall, 1e-9)),
            "batches": st.batches,
            "mean_batch": st.mean_batch,
        }
        rt.close()
    bit_identical = all(
        np.array_equal(a, b) for a, b in zip(ids["sync"], ids["async"]))
    out["overlap_speedup"] = (out["sync"]["wall_s"]
                              / max(out["async"]["wall_s"], 1e-9))
    out["bit_identical"] = bool(bit_identical)
    out["note"] = ("CPU-interpret container: XLA already multithreads each "
                   "dispatch, so the 2-worker pipeline lands within noise "
                   "of sync (~0.9-1.1x across runs); the overlap pays on "
                   "real devices where host->device transfer is the gap. "
                   "bit_identical is the invariant under test here.")
    return out


def _recall_vs_exact(db, tickets, k) -> np.ndarray:
    """Per-ticket recall@k vs the exact oracle — the SAME accounting for
    cache hits (which bypass the flush and carry no ExecutionMetrics) and
    for flushed misses, so the sweep's recall column is apples-to-apples."""
    out = []
    for t in tickets:
        gt, _ = exact_topk(db.concat(t.query.vid), t.query.concat(), k)
        got = set(int(i) for i in np.asarray(t.ids)[:k])
        out.append(len(got & set(int(i) for i in gt)) / k)
    return np.asarray(out)


def semantic_cache_summary(db, mint, day, cons, result, k) -> dict:
    """Device-resident semantic result cache (DESIGN.md §13): sweep the
    acceptance radius ε on a hot-item trace (near-duplicate hot traffic)
    and report the hit-rate vs measured-recall trade-off plus end-to-end
    p99 with/without the cache; then a tenant-skew trace to show per-tenant
    hot sets hitting in per-tenant namespaces. Recall for EVERY ticket —
    hit or flushed — is measured against the exact oracle; the θ floor is
    reported as frac_below_theta, cache hits included."""
    theta = cons.theta_recall
    trace = hot_item_trace(db, vid=(0,), n=240, qps=2000.0, n_hot=4,
                           p_hot=0.85, k=k, seed=7, noise=0.1,
                           qid_start=200_000)

    def run(eps, enabled=True):
        cfg = RuntimeConfig(max_batch=16, max_delay_ms=5.0, window=96,
                            min_window=48, cooldown_s=1e9,
                            drift_threshold=2.0, semcache=enabled,
                            semcache_epsilon=eps)
        rt = OnlineRuntime(db, mint, day, cons, result=result,
                           store=IndexStore(db, seed=0), config=cfg)
        rt.run_trace(trace[:32])  # warm kernels + plan cache
        t0 = time.time()
        tickets = rt.run_trace(trace)
        wall = time.time() - t0
        recalls = _recall_vs_exact(db, tickets, k)
        waits = np.asarray([t.wall_wait_ms for t in tickets])
        st = rt.stats()
        rt.close()
        return {
            "epsilon": eps if enabled else None,
            "hit_rate": (st["semcache"]["hit_rate"] if enabled else 0.0),
            "mean_recall": float(np.mean(recalls)),
            "min_recall": float(np.min(recalls)),
            "frac_below_theta": float(np.mean(recalls < theta)),
            "theta_recall_met": bool(np.mean(recalls) >= theta),
            "p50_wall_wait_ms": float(np.percentile(waits, 50)),
            "p99_wall_wait_ms": float(np.percentile(waits, 99)),
            "wall_s": float(wall),
            "batches": st["batcher"]["batches"],
            "semcache": (st["semcache"] if enabled else None),
        }

    baseline = run(0.0, enabled=False)
    sweep = [run(eps) for eps in (0.0, 0.05, 0.1, 0.2, 0.4)]
    # operating point: max hit-rate among sweep points still meeting theta
    ok = [s for s in sweep if s["theta_recall_met"]]
    op = max(ok, key=lambda s: s["hit_rate"]) if ok else None

    # multi-tenant: per-tenant hot sets must hit in per-tenant namespaces
    tenants = {"t0": day, "t1": day}
    skew = tenant_skew_trace(db, tenants, n=200, qps=2000.0, noisy="t1",
                             noisy_mult=4.0, k=k, seed=8, qid_start=300_000,
                             n_hot=3, p_hot=0.8, noise=0.1)
    mt = MultiTenantRuntime(
        [Tenant("t0", db, mint, day, cons, result=result),
         Tenant("t1", db, mint, day, cons, result=result)],
        budget_bytes=1 << 30,
        config=RuntimeConfig(max_batch=16, max_delay_ms=5.0, window=96,
                             min_window=48, cooldown_s=1e9,
                             drift_threshold=2.0, semcache=True,
                             semcache_epsilon=(op or sweep[2])["epsilon"]))
    mt_tickets = [mt.submit(tq.tenant, tq.query) for tq in skew]
    mt.drain()
    mt_recalls = _recall_vs_exact(db, mt_tickets, k)
    mt_stats = mt.stats()
    per_tenant = {tid: {"hit_rate": s["semcache"]["hit_rate"],
                        "namespaces": s["semcache"]["namespaces"],
                        "device_bytes": s["semcache"]["device_bytes"]}
                  for tid, s in mt_stats["tenants"].items()}
    mt.close()

    return {
        "trace": {"kind": "hot_item", "n": len(trace), "n_hot": 4,
                  "p_hot": 0.85, "noise": 0.1},
        "baseline_no_cache": baseline,
        "epsilon_sweep": sweep,
        "operating_point": op,
        "tenant_skew": {
            "n": len(skew),
            "mean_recall": float(np.mean(mt_recalls)),
            "frac_below_theta": float(np.mean(mt_recalls < theta)),
            "per_tenant": per_tenant,
        },
        "acceptance": {
            "hit_rate_ge_0.3_at_theta": bool(op and op["hit_rate"] >= 0.3),
            "p99_beats_baseline": bool(
                op and op["p99_wall_wait_ms"]
                < baseline["p99_wall_wait_ms"]),
            "eps0_recall_matches_baseline": bool(
                abs(sweep[0]["mean_recall"] - baseline["mean_recall"])
                < 1e-9),
        },
    }


def observability_summary(db, mint, day, cons, result, k) -> dict:
    """Observer-enabled hot-item run (DESIGN.md §14): per-ticket span
    trees across the async flush boundary, with the acceptance checks —
    at least one ticket with a COMPLETE stage set
    (enqueue/semcache_probe/flush_wait/dispatch/merge) whose stage sum is
    within 10% of end-to-end, async dispatch spans adopted into ticket
    roots — plus a bit-identity check against the observer-disabled
    run."""
    trace = hot_item_trace(db, vid=(0,), n=160, qps=2000.0, n_hot=4,
                           p_hot=0.85, k=k, seed=7, noise=0.1,
                           qid_start=400_000)

    def run_once(observe):
        cfg = RuntimeConfig(max_batch=16, max_delay_ms=5.0, window=96,
                            min_window=48, cooldown_s=1e9,
                            drift_threshold=2.0, semcache=True,
                            semcache_epsilon=0.1, async_flush=True,
                            workers=2, observe=observe)
        rt = OnlineRuntime(db, mint, day, cons, result=result,
                           store=IndexStore(db, seed=0), config=cfg)
        tickets = rt.run_trace(trace)
        ids = [np.asarray(t.result(timeout=60)) for t in tickets]
        obs = rt.observer if observe else None
        rt.close()
        return ids, obs

    ids_off, _ = run_once(False)
    ids_on, obs = run_once(True)

    need = {"enqueue", "semcache_probe", "flush_wait", "dispatch", "merge"}
    complete, covered = 0, 0
    for tr in obs.traces:
        if not need <= tr.stage_names():
            continue
        complete += 1
        if abs(tr.coverage() - 1.0) <= 0.10:
            covered += 1
    rep = obs_report(obs)
    return {
        "trace": {"kind": "hot_item", "n": len(trace)},
        "tickets_traced": len(obs.traces),
        "complete_span_trees": complete,
        "coverage_within_10pct": covered,
        "report": rep,
        "acceptance": {
            "complete_span_tree_ge_1": complete >= 1,
            "stage_sum_within_10pct": covered >= 1 and covered == complete,
            "disabled_bit_identical": bool(all(
                np.array_equal(a, b) for a, b in zip(ids_off, ids_on))),
        },
    }


def run(rows: int = 10000, steady_n: int = 120, drift_n: int = 180,
        k: int = 10, out_path: str = "BENCH_online.json") -> dict:
    db = make_database(rows, [("image", 96), ("title", 64),
                              ("description", 128), ("content", 96)],
                       seed=0)
    day = vid_workload(db, [(0,), (0, 1), (1,)], k=k, seed=0)
    night = vid_workload(db, [(2,), (2, 3), (3,)], k=k, seed=1)
    cons = Constraints(theta_recall=0.9, theta_storage=3)
    mint = Mint(db, index_kind="ivf", seed=0)
    result = mint.tune(day, cons)

    qps = 2000.0
    steady = steady_trace(db, day, n=steady_n, qps=qps, seed=3)
    t0 = steady_n / qps + 1.0
    drifted = diurnal_trace(db, day, night, n=drift_n, qps=qps, seed=4,
                            t0=t0, qid_start=10_000)

    variants = {}
    for name, retune in [("stale", False), ("retuned", True)]:
        store = IndexStore(db, seed=0)  # fresh store per variant
        variants[name] = run_variant(db, mint, day, cons, result, store,
                                     steady, drifted, retune=retune)
        tail = variants[name]["drift_tail"]
        print(f"{name:8s} drift-tail: mean_cost={tail['mean_cost']:.0f} "
              f"mean_recall={tail['mean_recall']:.3f} "
              f"exec_wall={tail['mean_exec_wall_ms']:.2f}ms "
              f"(retunes={len(variants[name]['retunes'])})")

    stale_cost = variants["stale"]["drift_tail"]["mean_cost"]
    retuned_cost = variants["retuned"]["drift_tail"]["mean_cost"]
    hit_rate = variants["retuned"]["steady_plan_cache"]["hit_rate"]
    out = {
        "scenario": "diurnal day->night drift",
        "rows": rows,
        "k": k,
        "theta_recall": cons.theta_recall,
        "theta_storage": cons.theta_storage,
        "steady_queries": steady_n,
        "drift_queries": drift_n,
        "variants": variants,
        "burst": burst_summary(db, mint, day, cons, result,
                               IndexStore(db, seed=0)),
        "async_flush": async_flush_overlap(db, mint, day, cons, result),
        "semantic_cache": semantic_cache_summary(db, mint, day, cons,
                                                 result, k),
        "observability": (obs := observability_summary(db, mint, day, cons,
                                                       result, k)),
        # registry snapshot from the observer-enabled run, surfaced
        # top-level so downstream consumers (auto-tuner, dashboards) don't
        # dig through the nested report
        "metrics": obs["report"]["metrics"],
        "drift_tail_cost_ratio_stale_over_retuned":
            stale_cost / max(retuned_cost, 1e-9),
        "acceptance": {
            "retuned_beats_stale_on_drift": retuned_cost < stale_cost,
            "retuned_recall_theta_met":
                variants["retuned"]["drift_tail"]["theta_recall_met"],
            "steady_plan_cache_hit_rate_gt_0.8": hit_rate > 0.8,
        },
    }
    with open(out_path, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out["acceptance"], indent=1))
    sc = out["semantic_cache"]
    print("semantic_cache:", json.dumps(sc["acceptance"]))
    if sc["operating_point"]:
        op = sc["operating_point"]
        print(f"  operating point eps={op['epsilon']}: "
              f"hit_rate={op['hit_rate']:.2f} "
              f"recall={op['mean_recall']:.3f} "
              f"p99={op['p99_wall_wait_ms']:.2f}ms "
              f"(baseline p99={sc['baseline_no_cache']['p99_wall_wait_ms']:.2f}ms)")
    print("observability:", json.dumps(out["observability"]["acceptance"]))
    print(f"cost ratio (stale/retuned) on drift tail: "
          f"{out['drift_tail_cost_ratio_stale_over_retuned']:.2f}x")
    return out


def main() -> None:
    start()  # compile cache + platform check
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=10000)
    ap.add_argument("--steady-n", type=int, default=120)
    ap.add_argument("--drift-n", type=int, default=180)
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--out", default="BENCH_online.json")
    args = ap.parse_args()
    run(rows=args.rows, steady_n=args.steady_n, drift_n=args.drift_n,
        k=args.k, out_path=args.out)


if __name__ == "__main__":
    main()
