"""What the wall-clock span log costs the host, per forward.

Times what ``CompiledCNN.forward`` adds with ``repro.obs.SPANS`` on:
its four clock readings and ``_record_forward`` (three spans with their
arguments), for a batch of the stream cell's shape, without the copy
or the jitted call. Also times a generation-0 collection with and
without the log's garbage-collector hook. Prints one JSON line; the
number is host CPU time only, so the script runs on the CPU backend:

    JAX_PLATFORMS=cpu PYTHONPATH=src python benchmarks/span_cost.py
"""
from __future__ import annotations

import gc
import json
import statistics

import numpy as np

from repro.configs import get_config
from repro.obs import SPANS, now_ns, set_spans
from repro.pipeline import ExecutionSpec, Serving, compile_cnn

N = 20_000          # forwards a sample
SAMPLES = 15


def forward_recording_ns(compiled, x) -> float:
    """Median over samples of the ns one forward's recording costs."""
    per = []
    for _ in range(SAMPLES):
        SPANS.clear()
        t_start = now_ns()
        for _ in range(N):
            t0 = now_ns()
            t1 = now_ns()
            t2 = now_ns()
            t3 = now_ns()
            compiled._record_forward(x, None, t0, t1, t2, t3)
        per.append((now_ns() - t_start) / N)
    return statistics.median(per)


def gen0_collection_ns(hook: bool) -> float:
    set_spans(hook)
    per = []
    for _ in range(SAMPLES):
        t_start = now_ns()
        for _ in range(1000):
            gc.collect(0)
        per.append((now_ns() - t_start) / 1000)
    set_spans(True)
    return statistics.median(per)


def main() -> None:
    cfg = get_config("alexnet").smoke()
    compiled = compile_cnn(cfg, ExecutionSpec(serving=Serving(batch=8)),
                           with_engine=False)
    x = np.zeros((8, 227, 227, 3), np.float32)
    set_spans(True)
    on = forward_recording_ns(compiled, x)
    SPANS.clear()
    print(json.dumps({
        "forward_recording_ns": on,
        "clock_read_ns": _clock_read_ns(),
        "gen0_collection_ns": {"hook": gen0_collection_ns(True),
                               "no_hook": gen0_collection_ns(False)},
    }, sort_keys=True), flush=True)


def _clock_read_ns() -> float:
    t_start = now_ns()
    for _ in range(N):
        now_ns()
    return (now_ns() - t_start) / N


if __name__ == "__main__":
    main()
