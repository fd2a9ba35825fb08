#!/usr/bin/env python3
"""The control, on the chip at the cell's own size: the cell run with one
guarantee of its configuration broken (``checkpoint_frequency = 2``: a
rarer checkpoint, the step that would tempt a later PR), or with
``--fault`` one of ``broken_child.py``'s faults planted in the program
(``skip_write``: an upload acknowledged and not written).  Every run has
to come out ``correct: false``.  The benchmark's own runs never run this.

    chiprun -- python benchmark/tests/control_on_chip.py \\
        --workload q7_inner_agg_backlog --seeds 101,102,103 --seconds 10
"""

import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import run  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--set", default="checkpoint_frequency=2")
    ap.add_argument("--fault", default=None)
    args = ap.parse_args()
    if args.fault:
        os.environ["BENCH_FAULT"] = args.fault
        how = {"child_script": os.path.join(BENCH, "tests",
                                            "broken_child.py")}
        what = f"fault={args.fault}"
    else:
        k, v = args.set.split("=")
        how = {"overrides": {k: int(v)}}
        what = args.set
    passed_as_correct = 0
    for seed in (int(s) for s in args.seeds.split(",")):
        result, _ = run.run_cell(args.workload, seed, args.seconds, False,
                                 **how)
        print(f"CONTROL {args.workload} {what} seed={seed} "
              f"correct={result['correct']} "
              f"checks={json.dumps(result['checks'])} "
              f"device={json.dumps(result['device'])}", flush=True)
        passed_as_correct += bool(result["correct"])
    print(f"CONTROL {args.workload}: {passed_as_correct} run(s) came out "
          "correct (has to be 0)", flush=True)
    return 1 if passed_as_correct else 0


if __name__ == "__main__":
    sys.exit(main())
