"""Quickstart: tune a multi-vector database with MINT and execute the plans.

    PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

from repro.core.types import Constraints, config_name
from repro.core.tuner import Mint, execute_workload, ground_truth_cache
from repro.data.vectors import make_database, make_workload
from repro.index.registry import IndexStore
from repro.launch.entry import start


def main():
    start()  # compile cache + platform check
    # a 4-column multi-modal database (e.g. image/title/description/content)
    db = make_database(12000, [("image", 128), ("title", 96),
                               ("description", 160), ("content", 192)], seed=0)
    workload = make_workload(db, "news", n_queries=6, k=50, seed=0)
    print("workload:", [q.name for q in workload.queries])

    mint = Mint(db, index_kind="hnsw", seed=0)
    constraints = Constraints(theta_recall=0.9, theta_storage=4)
    result = mint.tune(workload, constraints)
    print("\nrecommended configuration:", config_name(result.configuration))
    for qid in sorted(result.plans):
        print("  ", result.plans[qid].describe())

    # execute on real indexes and compare with the one-index-per-column baseline
    store = IndexStore(db, seed=0)
    gt = ground_truth_cache(db, workload)
    mint_m = execute_workload(db, store, workload, result, gt)
    pc = mint.per_column(workload, constraints)
    pc_m = execute_workload(db, store, workload, pc, gt)
    print(f"\nMINT      cost={mint_m.weighted_cost/1e6:.2f}M  "
          f"recall={mint_m.mean_recall:.3f}  storage={mint_m.storage:.0f}")
    print(f"PerColumn cost={pc_m.weighted_cost/1e6:.2f}M  "
          f"recall={pc_m.mean_recall:.3f}  storage={pc_m.storage:.0f}")
    print(f"speedup:  {pc_m.weighted_cost/max(mint_m.weighted_cost,1):.2f}x")


if __name__ == "__main__":
    main()
