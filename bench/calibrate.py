#!/usr/bin/env python3
"""Readings for the correctness limits, at a cell's own size, on the chip:
for each seed one run with a short window at the cell's own load, then the
numbers compared for the program and for the control (the reference one
precision step lower, in the program's place, held to the same limits), one
JSON line per seed.  Exits non-zero where the control passes the limits on
any seed: a limit it passes separates nothing.

    python3 bench/calibrate.py --workload cascade.qwen15_4b --seeds 11,12,13 \\
        --seconds 2

A limit lies above the program's largest reading over a dozen seeds or more
and below the control's smallest (see PERF.md)."""
import argparse
import io
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)
    from bench import harness
    cell = harness.load_cell(args.workload)
    control_passed = []
    for seed in (int(s) for s in args.seeds.split(",")):
        buf = io.StringIO()
        rc = harness.run(cell, seed=seed, seconds=args.seconds, trace=False,
                         t_process=time.perf_counter(), out=buf, control=True)
        if rc:
            return rc
        res = json.loads(buf.getvalue().strip().splitlines()[-1])
        print(json.dumps({"seed": seed, "correct": res["correct"],
                          "control_correct": res["control_correct"],
                          "program": {k: v["value"] for k, v in res["checks"].items()},
                          "control": res["control"]}), flush=True)
        if res["control_correct"]:
            control_passed.append(seed)
    if control_passed:
        print(f"calibrate: the control passed the limits on seeds {control_passed}",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
