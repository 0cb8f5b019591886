#!/usr/bin/env python3
"""The knee of an open-loop stream cell: the highest offered rate whose
p95 latency stays within the traffic's limit with no growing backlog.

    python3 bench/sweep.py --workload alexnet-stream \
        --rates 400,500,600 --seconds 8 [--seed N] [--write]

One process, one compile: the cell's system is built once, and the
stream driver offers each rate in turn for ``--seconds``. Per rate it
prints p50 / p95 / p99 (nearest rank, over all requests), the stalls
(batches over ten times the median batch time), the mean batch, how
long the queue took to drain after the last arrival, and the queue wait
of the first and last quarter of requests (a backlog that grows shows
as a last quarter that waits longer). ``--write`` puts 0.8 x the knee,
rounded down to a multiple of 10, into the traffic file as
``rate_per_s``.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LOAD_SHARE = 0.8                 # the cell offers this share of the knee
GROWTH = 1.5                     # last quarter's wait over the first's


def sustained(row: dict, limit_ms: float) -> bool:
    """Within the latency limit, and the queue did not grow."""
    return (row["p95_ms"] <= limit_ms
            and row["queue_last_ms"] <= GROWTH * row["queue_first_ms"] + 1.0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="alexnet-stream")
    ap.add_argument("--rates", required=True,
                    help="comma-separated offered rates, requests/s")
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--write", action="store_true",
                    help="write 0.8 x the knee into the traffic file")
    args = ap.parse_args(argv)
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    import jax

    from bench import harness
    harness.keep_logs_in_tmpdir()
    from bench.weights import make_weights

    bench = harness.load_benchmark()
    cell = harness.resolve(bench, args.workload)
    harness.check_device(cell.chips)
    harness.enable_compile_cache()
    cfg, traffic = cell.cfg, cell.traffic
    system_mod = harness.load_module("systems", cfg["system"])
    weights = make_weights(cfg, args.seed, system_mod.weight_sharding(cfg))
    jax.block_until_ready(weights)
    system = system_mod.System(cfg, traffic["batch_per_chip"], weights)
    driver = harness.load_module("drivers", traffic["driver"]).Driver(
        system, cfg, traffic, args.seed)
    driver.warm()
    harness.say(f"set-up {time.perf_counter() - T_START!r} s")
    limit = traffic["latency_limit_ms"]
    readers = {n: harness.load_module("metrics", n).read
               for n in ("p50_ms", "p95_ms", "stalls.stream")}
    rows = []
    for rate in (float(r) for r in args.rates.split(",")):
        driver.rate = rate
        w = driver.run(args.seconds)
        q = w.queue_s
        n4 = max(1, len(q) // 4)
        ctx = types.SimpleNamespace(window=w)
        row = {"rate_per_s": rate,
               **{n: read(ctx) for n, read in readers.items()},
               "p99_ms": w.notes["p99_ms"],
               "mean_batch": w.notes["mean_batch"],
               "drain_s": w.notes["drain_s"],
               "queue_first_ms": statistics.mean(q[:n4]) * 1e3,
               "queue_last_ms": statistics.mean(q[-n4:]) * 1e3,
               "loop_late_max_ms": w.notes["loop_late_max_ms"]}
        row["sustained"] = sustained(row, limit)
        rows.append(row)
        print(json.dumps(row), flush=True)
    knee = None                  # the last rate before the first miss
    for r in sorted(rows, key=lambda r: r["rate_per_s"]):
        if not r["sustained"]:
            break
        knee = r["rate_per_s"]
    rate = None if knee is None else int(LOAD_SHARE * knee) // 10 * 10
    print(json.dumps({"knee_per_s": knee, "cell_rate_per_s": rate,
                      "latency_limit_ms": limit}), flush=True)
    if args.write and rate is not None:
        path = os.path.join(ROOT, "bench", "traffic",
                            bench_traffic_name(bench, args.workload)
                            + ".json")
        traffic["rate_per_s"] = rate
        with open(path, "w") as f:
            json.dump(traffic, f, indent=2)
            f.write("\n")
    return 0 if knee is not None else 1


def bench_traffic_name(bench: dict, workload: str) -> str:
    return next(w["traffic"] for w in bench["workloads"]
                if w["name"] == workload)


if __name__ == "__main__":
    sys.exit(main())
