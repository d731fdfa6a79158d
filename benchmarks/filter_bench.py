"""Filtered-search benchmark (DESIGN.md §12) — two questions:

  1. access_paths : at each predicate selectivity in {0, 0.01, 0.1, 0.5, 1},
                    what do the three access paths (pre-filter gather,
                    keep-masked scan, 1/sel-inflated post-filter probe)
                    cost — and does the planner's AUTO choice track the
                    cheapest one? Acceptance: auto picks "pre" at <=1%
                    selectivity, a scan-shaped path (masked/post) at >=50%,
                    and auto's summed planner cost never exceeds the best
                    FIXED path's (no single fixed path wins everywhere, so
                    auto must beat each of them somewhere).
  2. roofline     : modeled HBM bytes for the filtered paths across the
                    same selectivity sweep (``launch.roofline``) — where
                    the pre-filter gather's byte crossover sits vs the
                    masked scan.

All filtered results are checked bit-identical to the brute-force filtered
oracle on the flat path (recall == 1.0); ANN post-filter recalls are
reported as measured. Emits BENCH_filter.json.

    PYTHONPATH=src python benchmarks/filter_bench.py [--rows 4000] [--quick]
"""
import argparse
import json
import time

import numpy as np

from repro.core.tuner import Mint
from repro.core.types import Constraints, Workload
from repro.data.vectors import make_database, make_queries
from repro.filter import Range
from repro.filter.attributes import synth_attributes
from repro.index.registry import IndexStore
from repro.launch.roofline import modeled_scan_bytes
from repro.serve.engine import BatchEngine
from repro.launch.entry import start

COLS = [("a", 48), ("b", 64)]
VIDS = [(0,), (0, 1), (1,)]
SELS = (0.0, 0.01, 0.1, 0.5, 1.0)
ACCESSES = ("pre", "masked", "post")


def quantile_pred(attrs, n_rows, sel, lo_q=0.2):
    """Range over the uniform "score" field hitting ~``sel`` of the rows."""
    vals = np.sort(attrs.take("score", np.arange(n_rows)))
    if sel <= 0.0:
        return Range("score", lo=float(vals[-1]) + 1.0,
                     hi=float(vals[-1]) + 2.0)
    if sel >= 1.0:
        return Range("score", lo=float(vals[0]) - 1.0,
                     hi=float(vals[-1]) + 1.0)
    lo_q = min(lo_q, 1.0 - sel)
    return Range("score", lo=float(np.quantile(vals, lo_q)),
                 hi=float(np.quantile(vals, lo_q + sel)))


def filtered_queries(queries, pred):
    from dataclasses import replace
    return [replace(q, predicate=pred) for q in queries]


def run_cell(engine, planner, config, queries, access):
    """Plan + execute one (selectivity, access) cell. Returns None when the
    forced access path is unavailable (e.g. "post" with no useful index)."""
    pairs = []
    for q in queries:
        try:
            plan = planner.plan(q, config, force_access=access)
        except ValueError:
            return None
        pairs.append((q, plan))
    t0 = time.time()
    metrics = engine.execute_batch(pairs)
    wall = (time.time() - t0) * 1e3
    return {
        "access": access or "auto",
        "chosen": sorted({p.access_path for _, p in pairs}),
        "est_cost": float(sum(p.est_cost for _, p in pairs)),
        "exec_cost": float(sum(m.cost for m in metrics)),
        "mean_recall": float(np.mean([m.recall for m in metrics])),
        "min_recall": float(np.min([m.recall for m in metrics])),
        "wall_ms": wall,
    }


def access_paths(rows, n_queries, k, seed):
    db = make_database(rows, COLS, seed=seed)
    attrs = synth_attributes(db.n_rows, seed=seed + 1)
    qs = make_queries(db, VIDS * (n_queries // len(VIDS) + 1), k=k,
                      seed=seed + 2)[:n_queries]
    wl = Workload(queries=qs, probs=np.ones(len(qs)))
    mint = Mint(db, index_kind="hnsw", seed=seed, attributes=attrs)
    cons = Constraints(theta_recall=0.9, theta_storage=3)
    result = mint.tune(wl, cons)
    planner = mint.planner(cons)
    store = IndexStore(db, seed=seed)
    engine = BatchEngine(db, store=store)
    engine.attach_filters(attrs, mint.selectivity_estimator())

    grid = []
    for sel in SELS:
        pred = quantile_pred(attrs, db.n_rows, sel)
        fqs = filtered_queries(qs, pred)
        true_sel = float(attrs.bitmap(pred, np.arange(db.n_rows)).mean())
        cell = {"target_selectivity": sel, "true_selectivity": true_sel,
                "estimated_selectivity": float(
                    mint.selectivity_estimator().estimate(pred)),
                "paths": {}}
        for access in ACCESSES + (None,):
            r = run_cell(engine, planner, result.configuration, fqs, access)
            if r is not None:
                cell["paths"][r["access"]] = r
        grid.append(cell)

    # acceptance: auto tracks the cheapest path and lands where the cost
    # model says it must at the extremes
    def auto_of(sel):
        return next(c for c in grid
                    if c["target_selectivity"] == sel)["paths"]["auto"]

    fixed_totals = {
        a: sum(c["paths"][a]["est_cost"] for c in grid if a in c["paths"])
        for a in ACCESSES if all(a in c["paths"] for c in grid)}
    auto_total = sum(c["paths"]["auto"]["est_cost"] for c in grid)
    low = auto_of(0.01)["chosen"]
    high = auto_of(0.5)["chosen"] + auto_of(1.0)["chosen"]
    exact_ok = all(c["paths"]["auto"]["min_recall"] == 1.0
                   or "post" in c["paths"]["auto"]["chosen"] for c in grid)
    acceptance = {
        "auto_pre_at_low_selectivity": low == ["pre"],
        "auto_scan_at_high_selectivity": all(a in ("masked", "post")
                                             for a in high),
        "auto_cost_beats_fixed": all(auto_total <= t * 1.0001
                                     for t in fixed_totals.values()),
        "auto_total_cost": auto_total,
        "fixed_total_costs": fixed_totals,
        "exact_or_post": exact_ok,
    }
    acceptance["ok"] = bool(acceptance["auto_pre_at_low_selectivity"]
                            and acceptance["auto_scan_at_high_selectivity"]
                            and acceptance["auto_cost_beats_fixed"]
                            and exact_ok)
    return {"rows": rows, "queries": len(qs), "k": k,
            "configuration": [str(s) for s in result.configuration],
            "grid": grid, "acceptance": acceptance}


def roofline_sweep(rows, B=64, d=112, k=10):
    out = []
    for sel in SELS:
        m = modeled_scan_bytes(B, rows, d, k, selectivity=sel)
        if "prefilter_bytes" not in m:
            continue
        out.append({"selectivity": sel,
                    "masked_filtered_bytes": m["masked_filtered_bytes"],
                    "prefilter_bytes": m["prefilter_bytes"],
                    "bitmap_bytes": m["bitmap_bytes"],
                    "pre_wins": m["prefilter_bytes"]
                    < m["masked_filtered_bytes"]})
    return out


def run(rows: int = 4000, n_queries: int = 9, k: int = 10, seed: int = 0,
        quick: bool = False, out: str = "BENCH_filter.json") -> dict:
    if quick:
        rows, n_queries = min(rows, 1200), 6
    t0 = time.time()
    report = {
        "access_paths": access_paths(rows, n_queries, k, seed),
        "roofline": roofline_sweep(rows),
    }
    report["wall_s"] = time.time() - t0
    with open(out, "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps(report["access_paths"]["acceptance"], indent=1))
    return report


def main() -> None:
    start()  # compile cache + platform check
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=4000)
    ap.add_argument("--n", type=int, default=9)
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--out", default="BENCH_filter.json")
    args = ap.parse_args()
    run(rows=args.rows, n_queries=args.n, k=args.k, seed=args.seed,
        quick=args.quick, out=args.out)


if __name__ == "__main__":
    main()
