import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512").strip()

"""Dry-run of the distributed vector-search serving plane (MINT's runtime).

Lowers ``search_step`` on the production mesh with a ShapeDtypeStruct
database and measures the collective schedule — the §Perf pair most
representative of the paper's technique:

  baseline  : gather-scores merge — every shard all-gathers its full local
              score matrix (Q, N_local) before the global top-k (the naive
              distributed top-k).
  optimized : tournament merge — per-shard local top-k first; only (Q, k)
              candidates cross the network.

Predicted collective ratio ≈ N_local / k (napkin math in EXPERIMENTS §Perf).
"""
import argparse
import json

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import collective_bytes, extract_cost
from repro.search.distributed import make_search_step
from repro.serve.columnstore import padded_device_bytes
from repro.serve.compiler import compile_batch, dispatch_plan


def make_naive_search_step(mesh, k: int, axis: str = "data"):
    def step(db, qvecs):
        def shard_fn(db_local, q_local):
            scores = q_local @ db_local.T                   # (Q, N_local)
            all_scores = jax.lax.all_gather(scores, axis)   # (S, Q, N_local)
            S, Q, NL = all_scores.shape
            flat = jnp.moveaxis(all_scores, 0, 1).reshape(Q, S * NL)
            vals, ids = jax.lax.top_k(flat, k)
            return vals, ids

        return jax.shard_map(shard_fn, mesh=mesh,
                             in_specs=(P(axis, None), P()),
                             out_specs=(P(), P()),
                             check_vma=False)(db, qvecs)
    return step


def lower_variant(name, step_fn, mesh, n_rows, dim, n_queries):
    db = jax.ShapeDtypeStruct((n_rows, dim), jnp.float32)
    q = jax.ShapeDtypeStruct((n_queries, dim), jnp.float32)
    with mesh:
        jitted = jax.jit(step_fn,
                         in_shardings=(NamedSharding(mesh, P("data", None)),
                                       NamedSharding(mesh, P())))
        compiled = jitted.lower(db, q).compile()
    colls = collective_bytes(compiled.as_text())
    cost = extract_cost(compiled)
    return {"variant": name, "collectives": colls, "cost": cost}


def plan_group_stats(n_queries: int, k: int, seed: int = 0) -> dict:
    """Dispatch accounting for a synthetic serving batch: how many kernel
    dispatches the plan-group compiler saves vs query-at-a-time serving.
    Uses hypothetical plans over a small schema — no data is touched."""
    import numpy as np
    from repro.core.types import IndexSpec, Query, QueryPlan

    rng = np.random.default_rng(seed)
    specs = [IndexSpec(vid=(c,), kind="ivf") for c in range(3)]
    pairs = []
    for qid in range(n_queries):
        vid = tuple(sorted(rng.choice(3, size=int(rng.integers(1, 4)),
                                      replace=False).tolist()))
        q = Query(qid=qid, vid=vid,
                  vectors={c: np.zeros(8, np.float32) for c in vid}, k=k)
        used = [s for s in specs if s.vid[0] in vid]
        eks = [int(rng.choice([k, 2 * k, 3 * k]))] * len(used)
        pairs.append((q, QueryPlan(qid, used, eks, 0.0, 1.0)))
    return dispatch_plan(compile_batch(pairs))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=1 << 24)
    ap.add_argument("--dim", type=int, default=256)
    ap.add_argument("--queries", type=int, default=1024)
    ap.add_argument("--k", type=int, default=100)
    ap.add_argument("--out", default="experiments/search_dryrun.json")
    ap.add_argument("--multi-pod", action="store_true")
    args = ap.parse_args()

    mesh = make_production_mesh(multi_pod=args.multi_pod)
    # what the serving column store actually pins: kernel-block padding plus
    # the mesh row-rounding are real device bytes (the memory-governor's
    # accounting unit) — the logical rows*dim*4 undercounts it
    resident = padded_device_bytes(args.rows, args.dim,
                                   row_mult=int(mesh.shape["data"]))
    logical = args.rows * args.dim * 4
    print(f"column store residency: {resident/2**30:.3f} GiB padded "
          f"({resident/logical:.4f}x logical)")
    out = []
    for name, fn in [("naive_gather_scores",
                      make_naive_search_step(mesh, args.k)),
                     ("tournament_topk",
                      make_search_step(mesh, args.k)),
                     # the serving engine's path: column-store padded rows
                     # masked via valid_n — same collective schedule as the
                     # plain tournament (the mask is shard-local)
                     ("columnstore_tournament",
                      make_search_step(mesh, args.k,
                                       valid_n=args.rows - args.rows // 100))]:
        rec = lower_variant(name, fn, mesh, args.rows, args.dim, args.queries)
        rec.update(rows=args.rows, dim=args.dim, queries=args.queries, k=args.k,
                   mesh="2x16x16" if args.multi_pod else "16x16",
                   padded_device_bytes=resident,
                   logical_device_bytes=logical)
        out.append(rec)
        tb = rec["collectives"]["total_bytes"]
        print(f"{name}: collective_bytes={tb/2**30:.3f} GiB "
              f"flops={rec['cost']['flops']:.3e}")
    groups = plan_group_stats(args.queries, args.k)
    groups["variant"] = "plan_group_compiler"
    out.append(groups)
    print(f"plan_group_compiler: {groups['queries']} queries -> "
          f"{groups['batched_scan_dispatches']} scan dispatches "
          f"(vs {groups['per_query_scan_dispatches']} per-query)")
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
