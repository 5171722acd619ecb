#!/usr/bin/env python3
"""Run one cell several times, one process per run, and print the spread
of each end-to-end metric: what a bound is set from.

    python3 chipbench/spread.py --workload <name> --seeds 1,2,3,4,5,6 \
        [--seconds S] [--trace 0|1] [--out results.jsonl]

Each run is ``chipbench/run.py`` as a benchmark run starts it. The spread of a
metric is the distance between the first and the third quartile
(``statistics.quantiles(values, n=4)``) as a share of the median.
"""

from __future__ import annotations

import argparse
import json
import statistics
import subprocess
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent


def spread(values) -> float:
    q1, med, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / med


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    seconds = args.seconds or json.loads(
        (HERE.parent / "BENCHMARK.json").read_text())["run_seconds"]
    rows = []
    for seed in args.seeds.split(","):
        cmd = [sys.executable, str(HERE / "run.py"), "--workload",
               args.workload, "--seed", seed, "--seconds", str(seconds),
               "--trace", str(args.trace)]
        p = subprocess.run(cmd, capture_output=True, text=True,
                           cwd=HERE.parent)
        tail = p.stderr.strip().splitlines()[-8:]
        print(f"seed {seed} rc {p.returncode}", *tail, sep="\n  ",
              file=sys.stderr, flush=True)
        if p.returncode != 0 or not p.stdout.strip():
            rows.append({"seed": seed, "rc": p.returncode})
            continue
        res = json.loads(p.stdout.strip().splitlines()[-1])
        rows.append({"seed": seed, "rc": 0, **res})
        print(json.dumps(rows[-1]), flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(rows[-1]) + "\n")
    ok = [r for r in rows if r.get("rc") == 0]
    names = sorted({k for r in ok for k in r["metrics"]})
    for name in names:
        vals = [r["metrics"][name]["value"] for r in ok
                if name in r["metrics"]]
        if len(vals) >= 2:
            print(json.dumps({"metric": name, "values": vals,
                              "median": statistics.median(vals),
                              "spread": spread(vals) if len(vals) >= 3
                              else None}), flush=True)
    print(json.dumps({"correct": [r.get("correct") for r in rows]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
