"""Multi-tenant serving benchmark (DESIGN.md §8). Three experiments:

  isolation : a noisy-neighbor tenant floods a capacity-limited engine
              (one micro-batch per service tick) while a light tenant keeps
              a steady trickle; per-tenant p99 queueing delay is compared
              between DEFICIT-ROUND-ROBIN flush selection and the FIFO
              baseline. DRR should hold the victim's p99 near one service
              interval regardless of the neighbor's backlog.
  governor  : the same tenant-skew trace under a device budget smaller
              than the tenants' combined working set; the governor must
              keep total padded device bytes <= budget (LRU spills back to
              host), with zero overcommits.
  efficiency: joint cross-tenant tuning (`core.tuner.tune_tenants`, greedy
              knapsack over per-tenant budget ladders) vs equal-split
              budgets, on aggregate estimated cost at recall >= theta.

Emits BENCH_tenant.json.

    PYTHONPATH=src python benchmarks/tenant_bench.py [--rows 1000]
"""
import argparse
import json

import numpy as np

from repro.core.tuner import Mint, TenantTask, tune_tenants
from repro.core.types import Constraints, Workload
from repro.data.vectors import make_database, make_queries
from repro.online import RuntimeConfig, tenant_skew_trace
from repro.serve.columnstore import ColumnStore
from repro.tenancy import MultiTenantRuntime, Tenant
from repro.launch.entry import start


def _wl(db, vids, k, seed):
    qs = make_queries(db, vids, k=k, seed=seed)
    return Workload(queries=qs, probs=np.ones(len(qs)))


def _tenants(rows, k):
    """Two tenants, separate databases: a light 'victim' and a 'noisy'
    neighbor with a wider schema (bigger resident columns)."""
    db_v = make_database(rows, [("v_img", 48), ("v_txt", 32)], seed=0)
    db_n = make_database(rows, [("n_img", 64), ("n_txt", 48),
                                ("n_meta", 32)], seed=7)
    wl_v = _wl(db_v, [(0,), (0, 1)], k=k, seed=0)
    wl_n = _wl(db_n, [(0,), (1, 2), (0, 1, 2)], k=k, seed=1)
    cons = Constraints(theta_recall=0.9, theta_storage=3)
    mint_v = Mint(db_v, index_kind="ivf", seed=0)
    mint_n = Mint(db_n, index_kind="ivf", seed=0)
    victim = Tenant("victim", db_v, mint_v, wl_v, cons,
                    result=mint_v.tune(wl_v, cons))
    noisy = Tenant("noisy", db_n, mint_n, wl_n, cons,
                   result=mint_n.tune(wl_n, cons))
    return victim, noisy


def serve_capacity_limited(rt: MultiTenantRuntime, trace, service_dt: float):
    """Replay arrivals against a fixed service cadence: the engine runs at
    most ONE micro-batch per ``service_dt`` (auto_flush=False + one poll
    per service tick), so a burst above capacity builds real backlog — the
    regime where flush-selection fairness matters."""
    tickets = []
    next_service = trace[0].t
    for tq in trace:
        while next_service <= tq.t:
            rt.tick(next_service)
            next_service += service_dt
        tickets.append(rt.submit(tq.tenant, tq.query, tq.t))
    while len(rt.batcher):
        rt.tick(next_service)
        next_service += service_dt
    return tickets


def wait_stats(tickets, tenant) -> dict:
    waits = [t.wait_ms for t in tickets if t.tenant == tenant]
    return {"queries": len(waits),
            "mean_wait_ms": float(np.mean(waits)),
            "p50_wait_ms": float(np.percentile(waits, 50)),
            "p99_wait_ms": float(np.percentile(waits, 99))}


def isolation_experiment(victim, noisy, k, budget_bytes, fair: bool) -> dict:
    cfg = RuntimeConfig(max_batch=8, max_delay_ms=1.0)
    rt = MultiTenantRuntime([victim, noisy], budget_bytes=budget_bytes,
                            config=cfg, fair=fair, auto_flush=False)
    trace = tenant_skew_trace(
        victim.db, {"victim": victim.workload, "noisy": noisy.workload},
        n=480, qps=400.0, noisy="noisy", noisy_mult=16.0, noisy_start=0.25,
        noisy_len=0.5, k=k, seed=3,
        dbs={"victim": victim.db, "noisy": noisy.db})
    service_dt = 0.010  # one batch per 10ms -> 800 q/s capacity
    tickets = serve_capacity_limited(rt, trace, service_dt)
    assert all(t.done for t in tickets)
    st = rt.stats()
    return {
        "policy": "drr" if fair else "fifo",
        "victim": wait_stats(tickets, "victim"),
        "noisy": wait_stats(tickets, "noisy"),
        # read-only snapshot (not the live stats object): consistent even
        # if a worker thread is mid-flush when we read
        "batcher": rt.batcher.snapshot_stats().as_dict(),
        "governor": st["governor"],
    }


def efficiency_experiment(rows, k) -> dict:
    """Tenant a: three disjoint wide queries, each accelerated only by its
    own narrow helper index (strictly decreasing budget ladder); tenant b:
    one wide query (flat ladder after one unit). Equal split starves a."""
    db_a = make_database(rows, [("a16", 16), ("a64", 64), ("b16", 16),
                                ("b64", 64), ("c16", 16), ("c64", 64)],
                         seed=0)
    db_b = make_database(max(rows * 4 // 5, 64),
                         [("x16", 16), ("x64", 64)], seed=7)
    tasks = {
        "a": TenantTask(Mint(db_a, index_kind="ivf", seed=0),
                        _wl(db_a, [(0, 1), (2, 3), (4, 5)], k=k, seed=0),
                        Constraints(theta_recall=0.85, theta_storage=4)),
        "b": TenantTask(Mint(db_b, index_kind="ivf", seed=0),
                        _wl(db_b, [(0, 1)], k=k, seed=1),
                        Constraints(theta_recall=0.85, theta_storage=2)),
    }
    joint = tune_tenants(tasks, global_storage=4)
    equal = tune_tenants(tasks, global_storage=4, equal_split=True)
    return {
        "global_storage": 4,
        "theta_recall": 0.85,
        "joint": {"allocations": joint.allocations,
                  "total_cost": joint.total_cost,
                  "total_storage": joint.total_storage,
                  "feasible": joint.feasible},
        "equal_split": {"allocations": equal.allocations,
                        "total_cost": equal.total_cost,
                        "total_storage": equal.total_storage,
                        "feasible": equal.feasible},
        "cost_ratio_equal_over_joint":
            equal.total_cost / max(joint.total_cost, 1e-9),
        "curves": {t: {str(b): c for b, c in curve.items()}
                   for t, curve in joint.curves.items()},
    }


def main() -> None:
    start()  # compile cache + platform check
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=1000)
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--out", default="BENCH_tenant.json")
    args = ap.parse_args()

    # budget: roughly one tenant's working set — forces cross-tenant spills
    probe = make_database(args.rows, [("n_img", 64), ("n_txt", 48),
                                      ("n_meta", 32)], seed=7)
    budget = 2 * ColumnStore(probe).device_bytes((0, 1, 2))

    # tenants are immutable across variants (runtimes never mutate the
    # specs): tune once, serve twice
    victim, noisy = _tenants(args.rows, args.k)
    variants = {}
    for fair in (True, False):
        v = isolation_experiment(victim, noisy, args.k, budget, fair=fair)
        variants[v["policy"]] = v
        print(f"{v['policy']:4s}: victim p99={v['victim']['p99_wait_ms']:.1f}ms "
              f"noisy p99={v['noisy']['p99_wait_ms']:.1f}ms "
              f"(governor: peak={v['governor']['peak_bytes']} "
              f"evictions={v['governor']['evictions']})")

    eff = efficiency_experiment(args.rows, args.k)
    print(f"joint {eff['joint']['allocations']} cost={eff['joint']['total_cost']:.0f} "
          f"vs equal {eff['equal_split']['allocations']} "
          f"cost={eff['equal_split']['total_cost']:.0f} "
          f"({eff['cost_ratio_equal_over_joint']:.2f}x)")

    drr, fifo = variants["drr"], variants["fifo"]
    gov_ok = all(v["governor"]["peak_bytes"] <= v["governor"]["budget_bytes"]
                 and v["governor"]["overcommits"] == 0
                 for v in variants.values())
    out = {
        "scenario": "tenant-skew noisy neighbor + joint budget split",
        "rows": args.rows,
        "k": args.k,
        "device_budget_bytes": budget,
        "isolation": variants,
        "efficiency": eff,
        "acceptance": {
            "drr_victim_p99_below_fifo":
                drr["victim"]["p99_wait_ms"] < fifo["victim"]["p99_wait_ms"],
            "joint_beats_equal_split_at_theta":
                eff["joint"]["feasible"]
                and eff["joint"]["total_cost"]
                < eff["equal_split"]["total_cost"],
            "governor_device_bytes_within_budget": gov_ok,
        },
    }
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out["acceptance"], indent=1))


if __name__ == "__main__":
    main()
