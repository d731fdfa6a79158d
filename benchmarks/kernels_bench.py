"""Streaming fused scan vs two-pass reference (DESIGN.md §11).

Sweeps table size N from VMEM-resident to beyond the old single-dispatch
VMEM limit (16 MiB score block) and reports, per N:

  - modeled HBM bytes moved by each path (``launch.roofline``) and their
    ratio — the headline: the streaming kernel never materializes the
    (B, N) score matrix, so at large N it moves several times fewer bytes
    while the two-pass score block no longer even fits in VMEM;
  - measured wall-clock per dispatch (real on TPU; interpret-mode numbers
    are capped at --measure-cap rows off-TPU and marked as such);
  - a bit-identical parity spot-check against the two-pass oracle, so the
    perf claim is never reported for a kernel that drifted.

Emits BENCH_kernels.json.

    PYTHONPATH=src python benchmarks/kernels_bench.py [--quick]
"""
import argparse
import json

import jax.numpy as jnp
import numpy as np

from repro.kernels.streaming.ops import streaming_fused_scan
from repro.kernels.streaming.ref import streaming_fused_scan_ref
from repro.launch.roofline import VMEM_BYTES, streaming_vs_twopass
from repro.launch.entry import start


def _parity_spot_check(seed: int = 0) -> dict:
    """One masked + delta-merge case, asserted bit-identical."""
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((9, 64)).astype(np.float32))
    db = jnp.asarray(rng.standard_normal((520, 64)).astype(np.float32))
    dlt = jnp.asarray(rng.standard_normal((70, 64)).astype(np.float32))
    dead = jnp.asarray(rng.random(520) < 0.1)
    kw = dict(k=25, metric="cosine", valid_n=500, dead_mask=dead,
              delta=dlt, delta_valid_n=60)
    vals, ids, _ = streaming_fused_scan(q, db, **kw)
    rvals, rids = streaming_fused_scan_ref(q, db, **kw)
    ok = (np.array_equal(np.asarray(vals), np.asarray(rvals))
          and np.array_equal(np.asarray(ids), np.asarray(rids)))
    assert ok, "streaming kernel diverged from two-pass oracle"
    return {"case": "B9 N520 d64 k25 cosine masked+delta", "bit_identical": ok}


def run(quick: bool = False, out: str = "BENCH_kernels.json",
        measure: bool = True, measure_cap: int | None = None) -> dict:
    ns = (2048, 8192, 65536) if quick else (2048, 8192, 32768, 65536)
    cap = measure_cap if measure_cap is not None else (1024 if quick else 4096)
    report = {
        "bench": "kernels",
        "vmem_bytes": VMEM_BYTES,
        "parity": _parity_spot_check(),
        "streaming_vs_twopass": streaming_vs_twopass(
            ns=ns, measure=measure, measure_n_cap=cap),
    }
    report["acceptance"] = report["streaming_vs_twopass"]["acceptance"]
    with open(out, "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps(report["acceptance"], indent=1))
    return report


def main() -> None:
    start()  # compile cache + platform check
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--no-measure", action="store_true",
                    help="modeled bytes only (skip wall-clock timing)")
    ap.add_argument("--measure-cap", type=int, default=None,
                    help="row cap for interpret-mode timing (off-TPU)")
    ap.add_argument("--out", default="BENCH_kernels.json")
    args = ap.parse_args()
    run(quick=args.quick, out=args.out, measure=not args.no_measure,
        measure_cap=args.measure_cap)


if __name__ == "__main__":
    main()
