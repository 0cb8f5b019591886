#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json on the chip(s) of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Set-up (weights from the seed, the program's compile, warm-up of the
cell's one batch shape) runs first; then the driver offers the cell's
traffic for ``--seconds``; then the reference checks a seeded sample of
the window's answers. The last line of standard output is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
end-to-end metrics, or with ``--trace 1`` the per-layer ones, read from
a profiler trace of the window), ``device``, with ``--trace 1``
``breakdown``, and last ``compared``: each number the comparison read,
beside its limit. Progress and the compared numbers go to standard
error.

Exits 3 with no result where JAX finds no accelerator or fewer chips
than the cell asks for.
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
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from bench import harness
    harness.keep_logs_in_tmpdir()
    cell = harness.resolve(harness.load_benchmark(), args.workload)
    try:
        result = harness.run(cell, args.seed, args.seconds,
                             bool(args.trace), T_START)
    except harness.NoDevice as e:
        harness.say(f"no result: {e}")
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
