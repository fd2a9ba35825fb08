#!/usr/bin/env python3
"""Sets of runs of one cell, each run a new process as the driver makes
them, and the spread of every metric: the distance between the first and
third quartile (``statistics.quantiles(values, n=4)``) as a share of the
median.  Result lines are kept under ``chiprun_out/sets/``.

    chiprun --timeout 3000 -- python benchmark/tests/sets_on_chip.py \\
        --workload q7_inner_agg_backlog --seeds 1,2,3,4,5,6 --sets 2 --seconds 40
"""

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def spread(values: list[float]) -> float:
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0)
    args = ap.parse_args()
    out_dir = os.path.join(ROOT, "chiprun_out", "sets")
    os.makedirs(out_dir, exist_ok=True)
    seeds = [int(s) for s in args.seeds.split(",")]
    bad = 0
    sets: list[dict[str, list[float]]] = []
    with open(os.path.join(out_dir, f"{args.workload}.jsonl"), "a") as log:
        for k in range(args.sets):
            values: dict[str, list[float]] = {}
            for seed in seeds:
                t0 = time.monotonic()
                p = subprocess.run(
                    [sys.executable, os.path.join(ROOT, "benchmark",
                                                  "run.py"),
                     "--workload", args.workload, "--seed", str(seed),
                     "--seconds", str(args.seconds),
                     "--trace", str(args.trace)],
                    cwd=ROOT, capture_output=True, text=True)
                wall = time.monotonic() - t0
                line = p.stdout.strip().splitlines()[-1] \
                    if p.stdout.strip() else ""
                print(f"SET {k} seed={seed} rc={p.returncode} "
                      f"wall={wall:.0f}s {line}", flush=True)
                if p.returncode != 0 or not line:
                    print(p.stderr[-3000:], flush=True)
                    bad += 1
                    continue
                r = json.loads(line)
                log.write(json.dumps({"set": k, "seed": seed, "wall_s": wall,
                                      "trace": args.trace, **r}) + "\n")
                log.flush()
                if not r["correct"]:
                    print(p.stderr[-3000:], flush=True)
                    bad += 1
                for name, m in r["metrics"].items():
                    values.setdefault(name, []).append(m["value"])
            sets.append(values)
    for name in sorted({n for s in sets for n in s}):
        per_set = [s.get(name, []) for s in sets]
        parts = []
        for v in per_set:
            if len(v) >= 2:
                parts.append(f"median {statistics.median(v):.6g} "
                             f"spread {100 * spread(v):.2f}%")
        print(f"SPREAD {args.workload} {name}: " + " | ".join(parts)
              + " | values " + json.dumps(per_set), flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
