"""The program's own wall-clock spans, placed on the trace's clock.

The program keeps spans of its entry layer and of Python's garbage
collector in memory (``repro.obs.trace.SPANS``): ``(name, start_ns,
end_ns, span_id, parent_id, args)`` on the host's ``perf_counter_ns``,
the clock of the benchmark's own ``bench.*`` spans. A traced run's
``Reduced`` holds those ``bench.*`` spans already shifted onto the
trace's clock, so the program's spans take the same shift. It is
recovered by pairing each ``cnn.forward`` with the ``bench.forward``
that called it, the two sequences slid against each other until their
start differences agree best, and taking the median of those
differences: off by the few microseconds between entering
``bench.forward`` and ``cnn.forward``. Then the spans are clipped to
the window.

A program that keeps no such spans (one older than the span log) gives
``None`` throughout, and so does a run with no pair to align.
"""
from __future__ import annotations

import statistics
import sys
from typing import List, Optional, Tuple

import numpy as np

from bench import tracing

PROGRAM_MODULE = "repro.obs.trace"
FORWARD = "cnn.forward"
BENCH_FORWARD = "bench.forward"

Span = Tuple[str, int, int, int, int, Optional[dict]]


def recorded() -> Optional[List[Span]]:
    """The spans the program in this process recorded; None where it
    keeps none. Imports nothing: a program that never loaded its span
    log recorded nothing."""
    log = getattr(sys.modules.get(PROGRAM_MODULE), "SPANS", None)
    if log is None:
        return None
    return log.read()["spans"]


def shift_ns(red: tracing.Reduced, spans: List[Span]) -> Optional[float]:
    """Trace time - program time, in ns, from the ``bench.forward`` spans
    of ``red`` and the ``cnn.forward`` spans of ``spans``; None with no
    pair. The shorter sequence slides along the longer; the offset
    whose start differences have the smallest median absolute
    deviation pairs them, and their median is the shift."""
    bench = np.array(sorted(s for n, s, _ in red.spans
                            if n == BENCH_FORWARD), dtype=np.float64)
    prog = np.array(sorted(s[1] for s in spans if s[0] == FORWARD),
                    dtype=np.float64)
    if not len(bench) or not len(prog):
        return None
    short, long_, sign = (bench, prog, 1.0) if len(bench) <= len(prog) \
        else (prog, bench, -1.0)
    n = len(short)
    best = None
    for k in range(len(long_) - n + 1):
        d = sign * (short - long_[k:k + n])
        med = float(np.median(d))
        spread = float(np.median(np.abs(d - med)))
        if best is None or spread < best[0]:
            best = (spread, med)
    return best[1]


def place(red: tracing.Reduced, spans: Optional[List[Span]]
          ) -> Optional[List[Span]]:
    """``spans`` on ``red``'s clock, clipped to its window (those wholly
    outside it dropped), in order of start; None where there are no
    program spans or no pair to align them by."""
    if red is None or not spans:
        return None
    shift = shift_ns(red, spans)
    if shift is None:
        return None
    lo, hi = red.window
    out = []
    for name, s, e, sid, parent, args in spans:
        s, e = int(s + shift), int(e + shift)
        inside = lo <= s <= hi if s == e else (s < hi and e > lo)
        if inside:
            out.append((name, max(s, lo), min(e, hi), sid, parent, args))
    return sorted(out, key=lambda t: t[1])


def read(ctx) -> Optional[List[Span]]:
    """The program's spans of a traced run's window, on its clock."""
    if ctx.trace is None:
        return None
    return place(ctx.trace, recorded())


def durations_ms(placed: List[Span], name: str) -> List[float]:
    return [(e - s) * 1e-6 for n, s, e, *_ in placed if n == name]


def median_ms(ctx, name: str) -> Optional[float]:
    """The median duration of the window's ``name`` spans, in ms."""
    placed = read(ctx)
    if placed is None:
        return None
    times = durations_ms(placed, name)
    return statistics.median(times) if times else None


def total_ms(ctx, name: str) -> Optional[float]:
    """The summed duration of the window's ``name`` spans, in ms: 0 where
    the program keeps spans but none of these fell in the window."""
    placed = read(ctx)
    if placed is None:
        return None
    return sum(durations_ms(placed, name))


def idle_overlap_share(red: tracing.Reduced, placed: List[Span],
                       name: str) -> Optional[float]:
    """Device idle time inside the union of the ``name`` spans, the mean
    over devices, as a share of the window."""
    if not red.devices or red.window[1] <= red.window[0]:
        return None
    cover = tracing.union([(s, e) for n, s, e, *_ in placed if n == name])
    if not cover:
        return 0.0
    starts = [s for s, _ in cover]
    total = 0
    for _, a, b in tracing.gaps(red):
        i = max(0, int(np.searchsorted(starts, a, side="right")) - 1)
        while i < len(cover) and cover[i][0] < b:
            s, e = cover[i]
            total += max(0, min(b, e) - max(a, s))
            i += 1
    return total / len(red.devices) / (red.window[1] - red.window[0])
