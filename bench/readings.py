#!/usr/bin/env python3
"""Readings a limit is set from: the program's compared numbers over
many seeds, and the control's (the reference one precision step down,
in the program's place) on the same images, in one process.

    python3 bench/readings.py --workload <cell> --seeds 11,12,13 \
        --seconds 2 [--out <file.json>]

Each seed is a whole run of the cell (its own weights, compile, a short
window at the cell's load, the comparison). Prints one line per seed
and, last, the largest program reading and the smallest control
reading of each control (``bench/references``' ``CONTROLS``).
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--out", help="also write the readings here (JSON)")
    args = ap.parse_args(argv)
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from bench import harness
    harness.keep_logs_in_tmpdir()
    cell = harness.resolve(harness.load_benchmark(), args.workload)
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        r = harness.run(cell, seed, args.seconds, False, t, control=True)
        row = {"seed": seed, "correct": r["correct"],
               "program": r["compared"], "control": r["control"],
               "run_s": time.perf_counter() - t}
        print(json.dumps(row), flush=True)
        rows.append(row)
    summary = {"program_max": {n: max(r["program"][n]["value"] for r in rows)
                               for n in rows[0]["program"]},
               "control_min": {c: min(r["control"][c] for r in rows)
                               for c in rows[0]["control"]}}
    out = {"workload": args.workload, "rows": rows, "summary": summary}
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
    print(json.dumps({"summary": summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
