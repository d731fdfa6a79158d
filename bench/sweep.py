"""Find a cell's knee: the highest offered rate whose backlog does not
grow over the window. One process per rate, each a plain run of the cell
with ``--rate`` in place of its mix's rate.

    python bench/sweep.py --workload <cell> --rates 10,20,30 --seconds 20

Prints one line per rate: offered and completed queries per second, the
backlog when the window closed, p50 and p95 latency, and whether the run
was correct.
"""
import argparse
import json
import re
import subprocess
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
WINDOW = re.compile(r"window: (\d+) due, (\d+) done inside, backlog (\d+)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True, help="comma-separated q/s")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        p = subprocess.run(
            [sys.executable, str(HERE / "run.py"), "--workload", args.workload,
             "--seed", str(args.seed + i), "--seconds", str(args.seconds),
             "--trace", "0", "--rate", str(rate)],
            capture_output=True, text=True, check=False)
        m = WINDOW.search(p.stderr)
        if p.returncode or m is None:
            print(json.dumps({"rate": rate, "rc": p.returncode,
                              "stderr": p.stderr[-2000:]}), flush=True)
            continue
        out = json.loads(p.stdout.strip().splitlines()[-1])
        due, inside, backlog = (int(x) for x in m.groups())
        met = out["metrics"]
        print(json.dumps({
            "rate": rate, "due": due,
            "completed_qps": inside / args.seconds, "backlog_at_close": backlog,
            "p50_ms": met.get("p50_ms", {}).get("value"),
            "p95_ms": met.get("p95_ms", {}).get("value"),
            "setup_s": met.get("setup_s", {}).get("value"),
            "correct": out["correct"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
