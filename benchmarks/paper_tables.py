"""Paper-table benchmarks (Fig 7/8, Fig 10, Fig 12-14, Table 3).

Scale note: the paper runs N=1M rows in C++; this Python/JAX reference
defaults to N=30k (flag-controlled) — speedups compress at small N because
graph-scan floors are a larger fraction of the database (EXPERIMENTS.md
§Paper-repro discusses the scale sensitivity).
"""
from __future__ import annotations

import time

from repro.core.types import Constraints
from repro.core.tuner import (Mint, execute_workload, ground_truth_cache)
from repro.data.vectors import make_database, make_workload, naive_database, news_database
from repro.index.registry import IndexStore

ROWS = []  # (name, us_per_call, derived)


def log(name, us, derived=""):
    ROWS.append((name, us, derived))
    print(f"{name},{us:.1f},{derived}")


def bench_endtoend(n_rows: int = 30000, kinds=("hnsw",), k: int = 100,
                   seed: int = 0):
    """Paper Fig 7/8: MINT vs PerColumn vs PerQuery, 4 workloads."""
    setups = [
        ("naive", naive_database(n_rows, seed=seed), 3, 0.9),
        ("bisimple", make_database(n_rows, seed=seed), 8, 0.9),
        ("bicomplex", make_database(n_rows, seed=seed), 8, 0.9),
        ("news", news_database(max(n_rows // 3, 5000), seed=seed), 4, 0.95),
    ]
    for kind in kinds:
        for wl_name, db, storage, theta in setups:
            wl = make_workload(db, wl_name, k=k, seed=seed)
            mint = Mint(db, index_kind=kind, seed=seed, min_sample_rows=4000)
            cons = Constraints(theta_recall=theta, theta_storage=storage)
            t0 = time.time()
            res = mint.tune(wl, cons)
            tune_s = time.time() - t0
            pc = mint.per_column(wl, cons)
            pq = mint.per_query(wl, cons)
            store = IndexStore(db, seed=seed)
            gt = ground_truth_cache(db, wl)
            out = {}
            for label, r in (("mint", res), ("percolumn", pc), ("perquery", pq)):
                m = execute_workload(db, store, wl, r, gt)
                out[label] = m
                log(f"e2e/{kind}/{wl_name}/{label}/cost", m.weighted_cost,
                    f"recall={m.mean_recall:.3f};storage={m.storage:.0f};"
                    f"wall_ms={m.weighted_wall_ms:.0f}")
            sp = out["percolumn"].weighted_cost / max(out["mint"].weighted_cost, 1)
            log(f"e2e/{kind}/{wl_name}/speedup_vs_percolumn", sp * 1e6,
                f"x{sp:.2f};tune_s={tune_s:.1f};"
                f"train_s={mint.estimators.train_seconds:.1f}")


def bench_storage_sweep(n_rows: int = 30000, seed: int = 0):
    """Paper Fig 10: latency falls as the storage budget grows."""
    db = make_database(n_rows, seed=seed)
    wl = make_workload(db, "bicomplex", k=100, seed=seed)
    mint = Mint(db, index_kind="hnsw", seed=seed, min_sample_rows=4000)
    store = IndexStore(db, seed=seed)
    gt = ground_truth_cache(db, wl)
    for budget in (7, 8, 9, 10):
        res = mint.tune(wl, Constraints(theta_recall=0.9, theta_storage=budget))
        m = execute_workload(db, store, wl, res, gt)
        log(f"storage_sweep/budget_{budget}/cost", m.weighted_cost,
            f"recall={m.mean_recall:.3f};n_indexes={len(res.configuration)}")


def bench_scalability(n_rows: int = 30000, seed: int = 0):
    """Paper Fig 12-14: tuner runtime vs workload size (linear-ish) and
    vs storage budget (flat, thanks to plan caching)."""
    db = make_database(n_rows, seed=seed)
    mint = Mint(db, index_kind="hnsw", seed=seed, min_sample_rows=4000)
    mint.train()
    log("scalability/train_estimators", mint.estimators.train_seconds * 1e6,
        f"sample_rate={mint.estimators.sample_rate:.3f}")
    for nq in (6, 12, 24):
        wl = make_workload(db, "bicomplex", n_queries=nq, k=100, seed=seed)
        t0 = time.time()
        res = mint.tune(wl, Constraints(theta_recall=0.9, theta_storage=8))
        dt = time.time() - t0
        calls = res.trace[-1].get("what_if_calls", 0)
        hits = res.trace[-1].get("cache_hits", 0)
        log(f"scalability/queries_{nq}/tune", dt * 1e6,
            f"what_if={calls};cache_hits={hits}")
    wl = make_workload(db, "bicomplex", k=100, seed=seed)
    for budget in (8, 10, 12):
        t0 = time.time()
        mint.tune(wl, Constraints(theta_recall=0.9, theta_storage=budget))
        log(f"scalability/storage_{budget}/tune", (time.time() - t0) * 1e6, "")


def bench_case_study(n_rows: int = 30000, seed: int = 0):
    """Paper Table 3: single-column vs multi-column plans on Naive."""
    db = naive_database(n_rows, seed=seed)
    wl = make_workload(db, "naive", k=100, seed=seed)
    mint = Mint(db, index_kind="diskann", seed=seed, min_sample_rows=4000)
    cons = Constraints(theta_recall=0.9, theta_storage=3)
    planner = mint.planner(cons)
    from repro.core.types import IndexSpec
    single = frozenset(IndexSpec((c,), "diskann") for c in range(3))
    multi = frozenset([IndexSpec((0,), "diskann"), IndexSpec((0, 1), "diskann"),
                       IndexSpec((1, 2), "diskann")])
    for q, _ in wl:
        ps = planner.plan(q, single)
        pm = planner.plan(q, multi)
        log(f"case_study/q{''.join(map(str, q.vid))}/single_total_ek",
            float(sum(ps.eks)), ";".join(f"{x.name}:{e}" for x, e in
                                         zip(ps.indexes, ps.eks)))
        log(f"case_study/q{''.join(map(str, q.vid))}/multi_total_ek",
            float(sum(pm.eks)), ";".join(f"{x.name}:{e}" for x, e in
                                         zip(pm.indexes, pm.eks)))


def bench_kernels():
    """Kernel micro-bench on the platform ``launch.entry.start`` chose: the
    TPU, or interpret mode on an explicit CPU run (correctness-mode
    timing — never a device number)."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.distance.kernel import batched_scores
    from repro.kernels.topk.kernel import topk_scores
    from repro.kernels.flash_attention.kernel import flash_attention
    from repro.kernels.distance.ref import batched_scores_ref

    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (64, 128), jnp.float32)
    db = jax.random.normal(key, (4096, 128), jnp.float32)
    for name, fn in [
        ("distance_pallas", lambda: batched_scores(q, db)),
        ("distance_ref", lambda: batched_scores_ref(q, db)),
    ]:
        fn()
        t0 = time.time()
        for _ in range(3):
            jax.block_until_ready(fn())
        log(f"kernels/{name}", (time.time() - t0) / 3 * 1e6, "64x4096x128")
    scores = jax.random.normal(key, (64, 4096), jnp.float32)
    topk_scores(scores, 100)
    t0 = time.time()
    jax.block_until_ready(topk_scores(scores, 100))
    log("kernels/topk_pallas", (time.time() - t0) * 1e6, "k=100")
    qa = jax.random.normal(key, (1, 4, 256, 64), jnp.float32)
    flash_attention(qa, qa, qa, bq=64, bkv=64)
    t0 = time.time()
    jax.block_until_ready(flash_attention(qa, qa, qa, bq=64, bkv=64))
    log("kernels/flash_attention_pallas", (time.time() - t0) * 1e6,
        "B1H4S256d64")
