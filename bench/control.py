"""Correctness readings of a cell: the program and its control, seed by
seed, in one process (set-up is long, so it is paid once for compiling).

    python bench/control.py --workload <cell> --seeds 1,2,3 --seconds 5

For each seed: the cell's set-up without the warm-up, a window of
``--seconds`` at the cell's load, then every window query compared with
the plain reference twice: as the program served it, and as the control
(the reference at bf16x3, one precision below the configuration's
HIGHEST, in the program's place) would have. Prints one JSON line per
seed and, last, the largest program reading and the smallest control
reading of each compared number: the two readings a limit is set
between. The benchmark's own runs never run the control.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

from run import REPO, use_compile_cache  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)

    import jax

    from bench import serve, spec

    cell = spec.cell(spec.load(REPO), args.workload, REPO)
    if jax.devices()[0].platform != "tpu":
        print("control: needs a TPU", file=sys.stderr)
        return 2
    use_compile_cache(jax)
    meter = serve.Meter()
    program: dict[str, list] = {}
    control: dict[str, list] = {}
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        out = serve.run_cell(cell, seed, args.seconds, False, t0,
                             meter=meter, warm=False, control=True)
        line = {"seed": seed, "attempted": out["attempted"],
                "program": out["checks"], "control": out["control"]["checks"],
                "control_failed": out["control"]["failed"]}
        print(json.dumps(line), flush=True)
        for name, c in out["checks"].items():
            program.setdefault(name, []).append(c["value"])
        for name, c in out["control"]["checks"].items():
            control.setdefault(name, []).append(c["value"])
    print(json.dumps({name: {"program_max": max(program[name]),
                             "control_min": min(control[name]),
                             "control_max": max(control[name])}
                      for name in program}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
