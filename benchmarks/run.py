# One function per paper table. Prints ``name,us_per_call,derived`` CSV.
import argparse
import time

from repro.launch.entry import start


def main() -> None:
    start()  # compile cache + platform check
    ap = argparse.ArgumentParser()
    ap.add_argument("--n-rows", type=int, default=30000,
                    help="database rows (paper: 1M in C++; see scale note)")
    ap.add_argument("--quick", action="store_true",
                    help="smaller rows for a fast smoke pass")
    args = ap.parse_args()
    n = 8000 if args.quick else args.n_rows

    from benchmarks import (autotune_bench, filter_bench, kernels_bench,
                            online_bench, paper_tables as T)

    t0 = time.time()
    print("name,us_per_call,derived")
    T.bench_kernels()
    # streaming-vs-twopass sweep -> BENCH_kernels.json (nightly artifact)
    kernels_bench.run(quick=args.quick, measure=not args.quick)
    # filtered access-path grid -> BENCH_filter.json (nightly artifact)
    filter_bench.run(rows=min(n, 4000), quick=args.quick)
    # online runtime: drift/retune + semantic cache + observability
    # (span-tree acceptance, metrics-registry snapshot) -> BENCH_online.json
    online_bench.run(rows=min(n, 4000))
    # whole-system auto-tuner: replayed hand sweep vs tuned Pareto front
    # (determinism gate + 10% acceptance) -> BENCH_autotune.json
    autotune_bench.run(quick=args.quick)
    T.bench_endtoend(n_rows=n, kinds=("hnsw", "diskann"))
    T.bench_storage_sweep(n_rows=n)
    T.bench_scalability(n_rows=n)
    T.bench_case_study(n_rows=n)
    print(f"# total benchmark wall time: {time.time() - t0:.0f}s")


if __name__ == "__main__":
    main()
