"""Open-loop stream (MLPerf Inference's Server scenario): independent
users send single images at a fixed rate, in real time.

Arrivals: ``rate_per_s * seconds`` requests whose gaps are the
exponential distribution's quantiles at (i + 0.5) / n, scaled to end at
``seconds`` and put in an order drawn from the seed. So every seed
offers the same count and the same gaps, as Poisson arrivals would on
average, in another order.

When the device is free, the batcher takes up to ``batch`` requests
that are due, pads them to the compiled batch, dispatches and fetches:
one batch in flight. A request's latency runs from its scheduled
arrival to its logits on the host, so a stall of the loop counts.
"""
from __future__ import annotations

import time

import numpy as np

from bench.stats import nearest_rank
from bench.weights import ORDER, SAMPLE, make_images, rng
from bench.window import NO_SPAN, Reservoir, Span, Window

SPIN_S = 0.0005                  # sleep until this close, then spin


def arrivals(rate: float, seconds: float, seed: int) -> np.ndarray:
    """Scheduled arrival times (s from the window's start), sorted."""
    n = max(1, int(round(rate * seconds)))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    gaps *= seconds / gaps.sum()
    return np.cumsum(rng(seed, ORDER).permutation(gaps))


class Driver:
    def __init__(self, system, cfg: dict, traffic: dict, seed: int):
        self.system, self.seed = system, seed
        self.batch = system.batch
        self.rate = traffic["rate_per_s"]
        self.n_check = traffic["check_requests"]
        self.pool = make_images(cfg, seed, traffic["pool_images"])
        self.buf = np.zeros((self.batch,) + self.pool.shape[1:],
                            self.pool.dtype)

    def warm(self) -> None:
        for _ in range(2):
            np.asarray(self.system.forward(self.buf))

    def run(self, seconds: float, span: Span = NO_SPAN) -> Window:
        due = arrivals(self.rate, seconds, self.seed)
        n, B = len(due), self.batch
        lat = np.zeros(n)
        queue = np.zeros(n)
        batch_s, late = [], []
        keep = Reservoir(self.n_check, rng(self.seed, SAMPLE))
        served = batches = 0
        start = time.perf_counter()
        while served < n:
            now = time.perf_counter() - start
            if due[served] > now:            # idle until the next arrival
                wait = due[served] - now - SPIN_S
                if wait > 0:
                    time.sleep(wait)
                while time.perf_counter() - start < due[served]:
                    pass
                late.append(time.perf_counter() - start - due[served])
                continue
            end = served + int(np.searchsorted(due[served:served + B], now,
                                               side="right"))
            k = end - served
            with span("bench.batch"):
                np.take(self.pool, np.arange(served, end) % len(self.pool),
                        axis=0, out=self.buf[:k])
                self.buf[k:] = 0.0
            t_disp = time.perf_counter() - start
            with span("bench.forward"):
                out = self.system.forward(self.buf)
            with span("bench.fetch"):
                logits = np.asarray(out)
            t_done = time.perf_counter() - start
            lat[served:end] = t_done - due[served:end]
            queue[served:end] = t_disp - due[served:end]
            batch_s.append(t_done - t_disp)
            for r in range(served, end):
                keep.offer((r, logits[r - served]))
            served, batches = end, batches + 1
        stop = time.perf_counter()
        late = np.asarray(late) if late else np.zeros(1)
        return Window(
            start=start, end=stop, attempted=n, failed=0, images=n,
            batches=batches,
            check_index=np.array([r % len(self.pool)
                                  for r, _ in keep.items]),
            check_logits=np.stack([l for _, l in keep.items]),
            latencies_s=lat.tolist(), queue_s=queue.tolist(),
            batch_s=batch_s,
            notes={"requests": n, "batches": batches,
                   "mean_batch": n / batches,
                   "p99_ms": nearest_rank(lat.tolist(), 0.99) * 1e3,
                   "loop_late_p99_ms": float(np.percentile(late, 99) * 1e3),
                   "loop_late_max_ms": float(late.max() * 1e3),
                   "drain_s": stop - start - float(due[-1])})

    def images(self, index: np.ndarray) -> np.ndarray:
        return self.pool[index]
