"""From a profiler trace to busy time, kernel time and idle gaps.

The run writes a JAX profiler trace (``.xplane.pb``) of its window with
the host tracer off: on, it instruments the runtime's host-to-device
copies and slows the timed path several times over. The benchmark's
own spans (``bench.*``) are kept on the host's ``perf_counter`` clock
instead, and put on the trace's clock by clock markers: a tiny jitted
``bench_clock`` call, run and waited for twice before the window and
twice after it. Each marker's device start lies inside its host span,
so trace time - host time is known to half a round trip.

This module reads the trace with ``jax.profiler.ProfileData`` and
keeps, on the trace's clock:

  * the window: the ``bench.window`` span;
  * per chip (plane ``/device:TPU:<n>``), every event of its ``XLA Ops``
    line, clipped to the window;
  * the benchmark's host spans.

Busy time is the union of a device's op intervals; the idle share is
1 - busy / window, averaged over the devices.

On a TPU an op event is named by its HLO instruction's text
(``%fused_conv.6 = f32[...] custom-call(...), custom_call_target=
"tpu_custom_call", ...``). An op's family is the instruction's name
without its number (``fused_conv``); a compiled Pallas kernel is a
``tpu_custom_call``, and its instruction is named after the jitted
wrapper that called ``pallas_call``.
"""
from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

WINDOW_SPAN = "bench.window"
CLOCK_SPAN = "bench.clock"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
CLOCK_MODULE = "jit_bench_clock"
DEVICE_PREFIX = "/device:TPU:"
NO_SPAN_NAME = "host outside bench spans"

Interval = Tuple[int, int]


@dataclass
class Op:
    family: str         # the HLO instruction's name without its number
    start: int          # ns
    end: int
    kernel: bool = False   # a compiled Pallas kernel (tpu_custom_call)


@dataclass
class Reduced:
    window: Interval
    devices: Dict[str, List[Op]] = field(default_factory=dict)
    spans: List[Tuple[str, int, int]] = field(default_factory=list)
    clock_err_ns: float = 0.0      # uncertainty of the host spans' shift

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


CUSTOM_KERNEL = 'custom_call_target="tpu_custom_call"'


def family(name: str) -> str:
    """``%fused_conv.6 = f32[...] ...`` -> ``fused_conv``; a plain name
    loses a trailing ``.<number>`` and ``.clone``."""
    head = name.split(" = ", 1)[0].lstrip("%").strip()
    parts = head.split(".")
    while len(parts) > 1 and (parts[-1].isdigit() or parts[-1] == "clone"):
        parts.pop()
    return ".".join(parts)


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def bench_clock(x):
    """The clock marker's computation (its module is ``jit_bench_clock``)."""
    return x * 2.0


def clock_shift(markers: List[int], host: List[Tuple[str, int, int]]
                ) -> Tuple[float, float]:
    """(trace time - host time, its uncertainty) in ns, from the device
    starts of the marker modules and the host's ``bench.clock`` spans, in
    order: the mean of each pair's device start - host midpoint, and the
    largest half-width of a host span."""
    clocks = [(a, b) for n, a, b in host if n == CLOCK_SPAN]
    if not clocks or len(clocks) != len(markers):
        raise ValueError(f"{len(markers)} clock markers on the device, "
                         f"{len(clocks)} on the host")
    shifts = [m - (a + b) / 2 for m, (a, b) in zip(markers, clocks)]
    return sum(shifts) / len(shifts), max((b - a) / 2 for a, b in clocks)


def load(path: str, host_spans: List[Tuple[str, int, int]]) -> Reduced:
    """Reduce the trace at ``path`` (an ``.xplane.pb``), with the host's
    spans (``perf_counter`` ns, markers among them) shifted onto it."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices: Dict[str, List[Op]] = {}
    markers: List[int] = []
    names: Dict[str, Tuple[str, bool]] = {}
    for plane in data.planes:
        if not plane.name.startswith(DEVICE_PREFIX):
            continue
        ops = []
        for line in plane.lines:
            if line.name == MODULES_LINE and not markers:
                markers = [int(ev.start_ns) for ev in line.events
                           if ev.name.startswith(CLOCK_MODULE + "(")]
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                fam = names.get(ev.name)
                if fam is None:
                    fam = names[ev.name] = (family(ev.name),
                                            CUSTOM_KERNEL in ev.name)
                s = int(ev.start_ns)
                ops.append(Op(fam[0], s, s + int(ev.duration_ns), fam[1]))
        devices[plane.name] = ops
    if devices:
        shift, err = clock_shift(sorted(markers), host_spans)
    else:                        # no chip traced: nothing to align with
        shift, err = 0.0, float("nan")
    spans = [(n, int(a + shift), int(b + shift)) for n, a, b in host_spans
             if n != CLOCK_SPAN]
    red = reduce(devices, spans)
    red.clock_err_ns = err
    return red


def reduce(devices: Dict[str, List[Op]],
           spans: List[Tuple[str, int, int]]) -> Reduced:
    """Clip device ops and host spans to the ``bench.window`` span."""
    wins = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if len(wins) != 1:
        raise ValueError(f"{len(wins)} {WINDOW_SPAN} spans in the trace")
    lo, hi = wins[0]
    clipped = {}
    for dev, ops in sorted(devices.items()):
        clipped[dev] = [Op(o.family, max(o.start, lo), min(o.end, hi),
                           o.kernel)
                        for o in ops if o.end > lo and o.start < hi]
    inner = sorted((n, max(s, lo), min(e, hi)) for n, s, e in spans
                   if n != WINDOW_SPAN and e > lo and s < hi)
    return Reduced(window=(lo, hi), devices=clipped,
                   spans=sorted(inner, key=lambda t: t[1]))


def union(intervals: List[Interval]) -> List[Interval]:
    """Sorted, merged intervals."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def busy_ns(ops: List[Op]) -> int:
    return sum(e - s for s, e in union([(o.start, o.end) for o in ops]))


def busy_s(red: Reduced) -> float:
    """Busy seconds, averaged over the devices in the trace."""
    if not red.devices:
        return 0.0
    return sum(busy_ns(ops) for ops in red.devices.values()) \
        / len(red.devices) * 1e-9


def idle_share(red: Reduced) -> Optional[float]:
    """1 - busy / window, the mean over devices; None with no device."""
    if not red.devices or red.window[1] <= red.window[0]:
        return None
    return 1.0 - busy_s(red) / red.window_s


def kernel_ops(red: Reduced, families) -> List[Op]:
    """Every compiled-kernel op (a ``tpu_custom_call``), on every device,
    whose family is one of ``families``."""
    return [o for ops in red.devices.values() for o in ops
            if o.kernel and o.family in families]


def top_ops(red: Reduced, n: int = 10) -> List[List]:
    """The ``n`` op families with the most device time, [family,
    seconds], summed over devices."""
    tot: Dict[str, int] = defaultdict(int)
    for ops in red.devices.values():
        for o in ops:
            tot[o.family] += o.end - o.start
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v * 1e-9] for k, v in best]


def gaps(red: Reduced) -> List[Tuple[str, int, int]]:
    """Idle gaps of every device inside the window: (device, start, end)."""
    out = []
    lo, hi = red.window
    for dev, ops in red.devices.items():
        t = lo
        for s, e in union([(o.start, o.end) for o in ops]):
            if s > t:
                out.append((dev, t, s))
            t = max(t, e)
        if hi > t:
            out.append((dev, t, hi))
    return out


def host_activity(red: Reduced, s: int, e: int,
                  starts: Optional[List[int]] = None) -> str:
    """The ``bench.*`` span that covers most of [s, e] on the host.
    ``starts`` (the spans' start times, as ``red.spans`` holds them)
    lets a caller with many gaps skip the spans before each."""
    if starts is None:
        starts = [a for _, a, _ in red.spans]
    cover: Dict[str, int] = defaultdict(int)
    # the host's spans nest at most one deep, so a span that covers s
    # starts at most a few entries before the first one after it
    first = max(0, bisect.bisect_right(starts, s) - 4)
    for i in range(first, len(red.spans)):
        name, a, b = red.spans[i]
        if b <= s:
            continue
        if a >= e:
            break
        cover[name] += min(b, e) - max(a, s)
    covered = sum(cover.values())
    if covered < (e - s) / 2:
        return NO_SPAN_NAME
    return max(cover.items(), key=lambda kv: kv[1])[0]


def idle_by_host(red: Reduced, n: int = 10) -> List[List]:
    """Idle device seconds, averaged over devices, by what the host was
    doing in each gap: [activity, seconds], most first."""
    tot: Dict[str, int] = defaultdict(int)
    starts = [a for _, a, _ in red.spans]
    for _, s, e in gaps(red):
        tot[host_activity(red, s, e, starts)] += e - s
    n_dev = max(1, len(red.devices))
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / n_dev * 1e-9] for k, v in best]
