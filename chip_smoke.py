"""Chip smoke test: MINT's main path once, on TPU, at the paper's 1M-row scale.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # four chips: the row-sharded scan only

One chip runs these phases in one process, in order:

  load        the paper's Naive database (Table 2: GloVe100 / SIFT1M /
              Yandex-T2I, widths 100/128/200) at 1M rows and its workload
              (vids (0,), (0,1), (1,2), (0,1,2); k = 100), made from --seed;
  tune_build  ``Mint(index_kind="ivf").tune`` under θ_recall 0.9 and a
              storage budget of 3 indexes, then an ``IndexStore`` build of
              every chosen index;
  exact_scan  ``BatchEngine(store=None)`` — the streaming Pallas scan over
              the device-resident columns — for 64 queries per vid at
              k = 100, checked against the numpy oracle ``exact_topk``;
  serve       ``OnlineRuntime`` over a 256-query steady trace, every
              ticket's recall measured against the numpy oracle.

``--chips 4`` runs one phase instead: the same table row-sharded over a
("data",) mesh of four chips, flat scans of all four vids through
``BatchEngine(mesh=)`` (the distributed tournament step), and the same
oracle check.

Each phase prints its wall seconds, the XLA compile requests it made (and
how many of them the persistent compile cache answered) and the device's
``peak_bytes_in_use`` so far. The last line of output is one JSON object
naming the device; it is printed only when every phase passed. Without a
TPU the script exits non-zero before any phase.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time
from pathlib import Path

ROWS = 1_000_000
K = 100
THETA = 0.9
SCAN_QUERIES = 64
TRACE_QUERIES = 256
WARM_TICKETS = 32
RTOL = 1e-4

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class Meter:
    """Per-phase wall seconds, XLA compile requests, persistent-cache hits
    and peak device memory, printed as one line per phase."""

    def __init__(self, jax):
        self.jax = jax
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event == _COMPILE_EVENT:
            self.compiles += 1

    def _on_event(self, event, **_):
        if event == _CACHE_HIT_EVENT:
            self.cache_hits += 1

    def peak_bytes(self) -> int:
        stats = [d.memory_stats() or {} for d in self.jax.local_devices()]
        return max(int(s.get("peak_bytes_in_use", 0)) for s in stats)

    @contextlib.contextmanager
    def phase(self, name: str):
        info: dict = {}
        c0, h0, t0 = self.compiles, self.cache_hits, time.perf_counter()
        yield info
        line = {"seconds": round(time.perf_counter() - t0, 3),
                "compiles": self.compiles - c0,
                "cache_hits": self.cache_hits - h0,
                "peak_bytes_in_use": self.peak_bytes(), **info}
        print(f"phase {name}: " + " ".join(f"{k}={v}" for k, v in line.items()),
              flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"check failed: {what}")


def check_against_oracle(host, queries, got_ids) -> dict:
    """Hold served top-k ids to the numpy oracle (``index.base.exact_topk``):
    rank by rank, the true score of the served id must equal the oracle's
    score to ``RTOL`` relative, so ids may differ only where the oracle's
    scores tie within that tolerance."""
    import numpy as np

    from repro.index.base import exact_topk

    worst, swaps = 0.0, 0
    for q, ids in zip(queries, got_ids):
        qvec = q.concat()
        o_ids, o_s = exact_topk(host, qvec, q.k)
        ids = np.asarray(ids, dtype=np.int64)
        check(ids.shape == o_ids.shape, f"q{q.qid}: {ids.shape} ids, "
              f"oracle has {o_ids.shape}")
        check(len(np.unique(ids)) == ids.size and ids.min() >= 0
              and ids.max() < host.shape[0], f"q{q.qid}: ids not distinct rows")
        s = host[ids] @ qvec
        rel = np.abs(s - o_s) / np.maximum(np.abs(o_s), 1e-6)
        worst = max(worst, float(rel.max()))
        check(bool((rel <= RTOL).all()),
              f"q{q.qid}: score off by {rel.max():.3g} relative at rank "
              f"{int(rel.argmax())}")
        swaps += int((ids != o_ids).sum())
    return {"max_rel_err": worst, "tie_swaps": swaps}


def load(meter, rows: int, seed: int):
    from repro.data.vectors import make_workload, naive_database
    from repro.serve.columnstore import ColumnStore

    with meter.phase("load") as info:
        db = naive_database(rows, seed=seed)
        workload = make_workload(db, "naive", k=K, seed=seed)
        vids = [q.vid for q in workload.queries]
        cs = ColumnStore(db)
        info.update(rows=db.n_rows, rows_cut=ROWS - db.n_rows, dims=db.dims,
                    vids=vids, padded_resident_bytes=sum(
                        cs.device_bytes(v) for v in vids))
    return db, workload, vids


def tune_build(meter, db, workload, seed: int):
    from repro.core.tuner import Mint
    from repro.core.types import Constraints
    from repro.index.registry import IndexStore

    with meter.phase("tune_build") as info:
        mint = Mint(db, index_kind="ivf", seed=seed)
        cons = Constraints(theta_recall=THETA, theta_storage=3)
        t0 = time.perf_counter()
        result = mint.tune(workload, cons)
        info["tune_seconds"] = round(time.perf_counter() - t0, 3)
        check(len(result.configuration) >= 1, "tuner chose no index")
        store = IndexStore(db, seed=seed)
        for spec in sorted(result.configuration, key=lambda s: s.vid):
            store.get(spec)
        info.update(configuration=sorted(s.name for s in result.configuration),
                    est_cost=round(float(result.est_workload_cost), 1))
    return mint, cons, result, store


def _flat_pairs(db, vid, seed: int):
    from repro.core.types import QueryPlan
    from repro.data.vectors import make_queries

    queries = make_queries(db, [vid] * SCAN_QUERIES, k=K, seed=seed)
    return queries, [(q, QueryPlan(q.qid, [], [], 0.0, 1.0)) for q in queries]


def exact_scan(meter, db, vids, seed: int, mesh=None):
    """Flat scans of every vid through ``BatchEngine(store=None)`` — one
    streaming-kernel launch per vid, or the distributed step under a
    mesh — held to the numpy oracle."""
    from repro.serve.engine import BatchEngine

    name = "exact_scan" if mesh is None else "sharded_scan"
    with meter.phase(name) as info:
        eng = BatchEngine(db, store=None, mesh=mesh)
        for i, vid in enumerate(vids):
            queries, pairs = _flat_pairs(db, vid, seed + 101 + i)
            ids = eng.search_batch(pairs)
            res = check_against_oracle(eng.cstore.host(vid), queries, ids)
            info["vid" + "".join(map(str, vid))] = res
        info["scan_dispatches"] = eng.counters.scan
        check(eng.counters.scan == len(vids), "one scan dispatch per vid")
        if mesh is not None:
            col = eng.cstore.device(vids[-1])
            info["shards"] = len(col.data.sharding.device_set)
            check(info["shards"] == mesh.size, "columns are row-sharded")
    return eng


def serve(meter, db, mint, workload, cons, result, store, cstore, seed: int):
    import numpy as np

    from repro.online import OnlineRuntime, RuntimeConfig, steady_trace
    from repro.serve.engine import BatchEngine

    with meter.phase("serve") as info:
        cfg = RuntimeConfig(measure=True)
        # a rate at which a full micro-batch arrives within one flush deadline
        qps = cfg.max_batch / (cfg.max_delay_ms / 1e3)
        trace = steady_trace(db, workload, n=TRACE_QUERIES, qps=qps,
                             seed=seed + 7, qid_start=10_000)
        engine = BatchEngine(db, store=store, cstore=cstore)
        rt = OnlineRuntime(db, mint, workload, cons, result=result,
                           store=store, engine=engine, config=cfg)
        tickets = rt.run_trace(trace[:WARM_TICKETS])
        c_warm = meter.compiles
        tickets += rt.run_trace(trace[WARM_TICKETS:])
        info["compiles_after_first_32"] = meter.compiles - c_warm
        rt.close()
        check(len(tickets) == len(trace) and all(
            t.done and t.ids is not None and t.metrics is not None
            for t in tickets), "every ticket completes")
        recalls = np.asarray([t.metrics.recall for t in tickets])
        st = rt.stats()
        info.update(tickets=len(tickets), batches=st["batcher"]["batches"],
                    mean_recall=round(float(recalls.mean()), 4),
                    frac_below_theta=round(float((recalls < THETA).mean()), 4),
                    dispatches=st["dispatches"])
        check(float(recalls.mean()) >= THETA,
              f"mean recall {recalls.mean():.4f} < θ {THETA}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the row-sharded scan over four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devices[0].platform!r}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2

    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.launch.entry import use_compile_cache

    print(f"device: {devices[0].device_kind} x{len(devices)}; compile cache "
          f"{use_compile_cache()}", flush=True)
    meter = Meter(jax)
    db, workload, vids = load(meter, ROWS, args.seed)
    if args.chips == 4:
        from repro.launch.mesh import make_data_mesh
        exact_scan(meter, db, vids, args.seed, mesh=make_data_mesh(4))
    else:
        mint, cons, result, store = tune_build(meter, db, workload, args.seed)
        eng = exact_scan(meter, db, vids, args.seed)
        serve(meter, db, mint, workload, cons, result, store, eng.cstore,
              args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
