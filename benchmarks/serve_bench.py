"""Per-query vs batched plan execution on the quickstart workload.

Measures queries/sec, kernel-dispatch counts, and p50/p99 latency —
cold (first pass on a fresh engine: column-store materialization + jit
compilation) reported separately from steady-state — for
  - per_query : one engine call per (query, plan) pair (the old
                query-at-a-time serving form, B=1 groups), and
  - batched   : the whole request batch compiled into plan groups
                (one scan dispatch per (group, index) — serve.compiler).

Emits BENCH_serve.json next to the repo root.

    PYTHONPATH=src python benchmarks/serve_bench.py [--rows 12000] [--reps 3]
"""
import argparse
import json
import time

import numpy as np

from repro.core.types import Constraints
from repro.core.tuner import Mint
from repro.data.vectors import make_database, make_queries, make_workload
from repro.index.registry import IndexStore
from repro.serve.compiler import compile_batch, dispatch_plan
from repro.serve.engine import BatchEngine
from repro.launch.entry import start


def _percentiles(lat_ms: list[float]) -> dict:
    a = np.asarray(lat_ms)
    return {"p50_ms": float(np.percentile(a, 50)),
            "p99_ms": float(np.percentile(a, 99)),
            "mean_ms": float(a.mean())}


def _one_pass(engine, pairs, batched: bool) -> list[float]:
    """Per-query latencies (ms) for one pass over the request batch."""
    if batched:
        t0 = time.time()
        engine.search_batch(pairs)
        per_q = (time.time() - t0) * 1e3 / len(pairs)
        return [per_q] * len(pairs)  # amortized batch latency
    lat = []
    for q, plan in pairs:
        t0 = time.time()
        engine.search_batch([(q, plan)])
        lat.append((time.time() - t0) * 1e3)
    return lat


def bench(pairs, engine_factory, reps: int, batched: bool) -> dict:
    """Cold vs steady-state, separated: the first pass on a fresh engine
    pays one-off work — device column-store materialization and any jit
    compilation not yet process-cached — which used to pollute the
    per-query p99 (127ms cold vs 4.3ms p50 in the old single-bucket
    numbers). Steady-state reps reuse the warmed engine."""
    engine = engine_factory()
    cold = _one_pass(engine, pairs, batched)  # warmup pass, timed separately

    lat: list[float] = []
    qps_runs: list[float] = []
    for _ in range(reps):
        engine.counters.reset()
        t_run0 = time.time()
        lat.extend(_one_pass(engine, pairs, batched))
        qps_runs.append(len(pairs) / (time.time() - t_run0))
    out = {"cold": _percentiles(cold), "steady": _percentiles(lat)}
    out["steady"]["qps"] = float(np.mean(qps_runs))
    out["dispatches"] = engine.counters.as_dict()  # one steady pass
    return out


def main() -> None:
    start()  # compile cache + platform check
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=12000)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--burst", type=int, default=16,
                    help="extra same-plan queries appended per hot vid")
    ap.add_argument("--out", default="BENCH_serve.json")
    args = ap.parse_args()

    # the quickstart schema/workload, served with the TPU-native index kind
    db = make_database(args.rows, [("image", 128), ("title", 96),
                                   ("description", 160), ("content", 192)],
                       seed=0)
    workload = make_workload(db, "news", n_queries=6, k=50, seed=0)
    mint = Mint(db, index_kind="ivf", seed=0)
    result = mint.tune(workload, Constraints(theta_recall=0.9, theta_storage=4))
    store = IndexStore(db, seed=0)

    pairs = [(q, result.plans[q.qid]) for q, _ in workload]
    # burst traffic: many users hitting the hottest plan signature
    hot = workload.queries[-1]
    burst = make_queries(db, [hot.vid] * args.burst, k=hot.k, seed=7)
    pairs = pairs + [(bq, result.plans[hot.qid]) for bq in burst]

    stats = dispatch_plan(compile_batch(pairs))
    print(f"{stats['queries']} queries -> {stats['groups']} plan groups; "
          f"scan dispatches {stats['per_query_scan_dispatches']} per-query "
          f"vs {stats['batched_scan_dispatches']} batched")

    shared_store = store  # index build cost excluded from both variants
    per_query = bench(pairs, lambda: BatchEngine(db, store=shared_store),
                      args.reps, batched=False)
    batched = bench(pairs, lambda: BatchEngine(db, store=shared_store),
                    args.reps, batched=True)

    result_json = {
        "workload": "quickstart-news+burst",
        "rows": args.rows,
        "queries": stats["queries"],
        "plan_groups": stats["groups"],
        "per_query": per_query,
        "batched": batched,
        "throughput_speedup": (batched["steady"]["qps"]
                               / max(per_query["steady"]["qps"], 1e-9)),
        "dispatch_reduction": (stats["per_query_scan_dispatches"]
                               / max(stats["batched_scan_dispatches"], 1)),
    }
    with open(args.out, "w") as f:
        json.dump(result_json, f, indent=1)
    print(json.dumps(result_json, indent=1))


if __name__ == "__main__":
    main()
