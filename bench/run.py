"""Run one cell of ``BENCHMARK.json`` on the chip this process finds.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Prints the set-up's and the window's readings on standard error, then
each compared number beside its limit as the last lines there, and the
result as one JSON object, the last line of standard output. With
``--trace 0`` its metrics are the cell's end-to-end metrics; with
``--trace 1`` the window is traced and they are its per-layer metrics.
Exits non-zero, printing no result, without a TPU or with fewer chips
than the cell asks for.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

REPO = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(REPO), str(REPO / "src")]

# fixed, inside the checkout: the path is part of the cache's key
CACHE_DIR = REPO / ".jax_cache" / "bench"


def use_compile_cache(jax) -> None:
    """Every program goes to the persistent cache, however quick to
    compile or small, so that only a checkout's first run compiles."""
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rate", type=float, default=None,
                    help="offered queries/s in place of the mix's (the knee "
                         "sweep, bench/sweep.py)")
    args = ap.parse_args(argv)

    import jax

    from bench import serve, spec

    cell = spec.cell(spec.load(REPO), args.workload, REPO)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"bench: {args.workload} needs {cell.chips} TPU chip(s); JAX "
              f"found {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    spec.peaks(devices[0].device_kind, REPO)  # an unknown chip is an error
    use_compile_cache(jax)
    out = serve.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                         T_START, rate=args.rate)
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
