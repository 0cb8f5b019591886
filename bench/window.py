"""What a driver hands back from its measured window."""
from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field
from typing import Callable, ContextManager, Dict, List, Tuple

import numpy as np

Span = Callable[[str], ContextManager]
NO_SPAN: Span = lambda name: contextlib.nullcontext()  # noqa: E731


class SpanLog:
    """A :data:`Span` that keeps (name, start_ns, end_ns) on the host's
    ``perf_counter`` clock, in memory, for the trace reduction."""

    def __init__(self):
        self.spans: List[Tuple[str, int, int]] = []

    @contextlib.contextmanager
    def __call__(self, name: str):
        t0 = time.perf_counter_ns()
        try:
            yield
        finally:
            self.spans.append((name, t0, time.perf_counter_ns()))


@dataclass
class Window:
    """One measured window.

    ``start``/``end`` are host-clock seconds (``time.perf_counter``): the
    first dispatch and the last logits on the host. ``check_index`` are
    indices into the driver's image pool and ``check_logits`` the logits
    the timed path returned for them: the answers the comparison reads.
    """
    start: float
    end: float
    attempted: int
    failed: int
    images: int                                   # logits on the host
    batches: int
    check_index: np.ndarray
    check_logits: np.ndarray
    latencies_s: List[float] = field(default_factory=list)   # per request
    queue_s: List[float] = field(default_factory=list)       # per request
    batch_s: List[float] = field(default_factory=list)       # per batch
    notes: Dict[str, float] = field(default_factory=dict)    # for stderr

    @property
    def seconds(self) -> float:
        return self.end - self.start


class Reservoir:
    """A uniform sample of ``k`` items from a stream of unknown length,
    drawn from ``rng``: the same seed and count keep the same items."""

    def __init__(self, k: int, rng: np.random.Generator):
        self.k, self.rng, self.seen = k, rng, 0
        self.items: list = []

    def offer(self, item) -> None:
        if len(self.items) < self.k:
            self.items.append(item)
        else:
            j = int(self.rng.integers(self.seen + 1))
            if j < self.k:
                self.items[j] = item
        self.seen += 1
