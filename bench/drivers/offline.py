"""Offline traffic (MLPerf Inference's Offline scenario): every request
is there at once, and the driver keeps the system busy.

Batches of the compiled size come from a host pool made from the seed
before the window. At most ``in_flight`` batches are out at a time: the
driver dispatches the next before it fetches the oldest. A batch counts
when its logits reach the host.
"""
from __future__ import annotations

import time

import numpy as np

from bench.weights import SAMPLE, make_images, rng
from bench.window import NO_SPAN, Reservoir, Span, Window


class Driver:
    def __init__(self, system, cfg: dict, traffic: dict, seed: int):
        self.system, self.seed = system, seed
        self.batch = system.batch
        self.n_pool = traffic["pool_batches"]
        self.in_flight = traffic["in_flight"]
        self.n_check = traffic["check_batches"]
        self.pool = make_images(cfg, seed, self.n_pool * self.batch)

    def _x(self, i: int) -> np.ndarray:
        j = (i % self.n_pool) * self.batch
        return self.pool[j:j + self.batch]

    def warm(self) -> None:
        """Run the one shape the window uses until it is compiled and
        loaded, with as many batches out as the window keeps."""
        outs = [self.system.forward(self._x(i))
                for i in range(self.in_flight)]
        for out in outs:
            np.asarray(out)

    def run(self, seconds: float, span: Span = NO_SPAN) -> Window:
        keep = Reservoir(self.n_check, rng(self.seed, SAMPLE))
        out = []                       # (device logits, batch number)
        dispatched = images = 0
        start = time.perf_counter()
        deadline = start + seconds
        while True:
            if time.perf_counter() < deadline:
                with span("bench.batch"):
                    x = self._x(dispatched)
                with span("bench.forward"):
                    out.append((self.system.forward(x), dispatched))
                dispatched += 1
                if len(out) < self.in_flight:
                    continue
            if not out:
                break
            logits, i = out.pop(0)
            with span("bench.fetch"):
                logits = np.asarray(logits)
            images += len(logits)
            keep.offer((i, logits))
        end = time.perf_counter()
        index = np.concatenate([(i % self.n_pool) * self.batch
                                + np.arange(self.batch)
                                for i, _ in keep.items])
        return Window(start=start, end=end,
                      attempted=dispatched * self.batch,
                      failed=dispatched * self.batch - images,
                      images=images, batches=dispatched,
                      check_index=index,
                      check_logits=np.concatenate(
                          [l for _, l in keep.items]))

    def images(self, index: np.ndarray) -> np.ndarray:
        return self.pool[index]
