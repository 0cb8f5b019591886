"""The program's spans placed on a synthetic trace's clock, and the
metrics that read them."""
import sys
from types import SimpleNamespace

import pytest

from bench import harness, program_spans, tracing
from bench.tracing import Op

MS = 1_000_000
US = 1_000
SHIFT = -500_000_000_000          # trace time - program time, planted
BENCH_STARTS_MS = [10, 23, 37.5, 52, 60.25, 78]


def _trace(devices=None, extra_spans=()):
    """A 100 ms window; the host batches, dispatches in ``bench.forward``
    (1 ms) at irregular times, and fetches."""
    spans = [("bench.window", 0, 100 * MS)]
    for s in BENCH_STARTS_MS:
        t = int(s * MS)
        spans += [("bench.forward", t, t + 1 * MS),
                  ("bench.fetch", t + 1 * MS, t + 3 * MS)]
    return tracing.reduce(devices or {}, spans + list(extra_spans))


def _program(jitter_ns=(100, 600, 250, 400, 150, 550)):
    """What the program recorded on its own clock: two warm-up forwards
    before the window, then one forward (with its copy and dispatch) a
    few hundred ns into each ``bench.forward``."""
    spans, sid = [], 1
    starts = [-30 * MS, -20 * MS] + [int(s * MS) + j for s, j in
                                     zip(BENCH_STARTS_MS, jitter_ns)]
    for i, t in enumerate(starts):
        t -= SHIFT
        args = {"batch": i, "images": 8, "bytes": 8 << 20}
        spans.append(("cnn.forward", t, t + 900 * US, sid, 0, args))
        spans.append(("cnn.h2d", t + 5 * US, t + 400 * US, sid + 1, sid,
                      {"batch": i, "bytes": 8 << 20, "devices": 1}))
        spans.append(("cnn.dispatch", t + 400 * US, t + 900 * US, sid + 2,
                      sid, {"batch": i}))
        sid += 3
    return spans


def test_recovers_the_planted_shift_within_a_microsecond():
    shift = program_spans.shift_ns(_trace(), _program())
    # the median of the jitter (between bench.forward and cnn.forward)
    # is the only error
    assert abs(shift - SHIFT) <= 1 * US
    assert abs(shift - (SHIFT - 325)) < 1


def test_a_program_with_fewer_forwards_still_aligns():
    """The program's buffer dropped its oldest spans: it holds fewer
    forwards than the window ran."""
    prog = [s for s in _program() if s[5]["batch"] >= 4]
    assert abs(program_spans.shift_ns(_trace(), prog) - SHIFT) <= 1 * US


def test_spans_outside_the_window_are_clipped():
    late = 98 * MS - SHIFT
    prog = _program() + [
        ("py.gc", late, late + 5 * MS, 99, 0, {"generation": 2}),
        ("py.gc", 120 * MS - SHIFT, 121 * MS - SHIFT, 100, 0, {}),
        ("cnn.retrace", 130 * MS - SHIFT, 130 * MS - SHIFT, 101, 0, {}),
        ("cnn.retrace", 50 * MS - SHIFT, 50 * MS - SHIFT, 102, 0, {})]
    placed = program_spans.place(_trace(), prog)
    lo, hi = 0, 100 * MS
    assert all(lo <= s <= e <= hi for _, s, e, *_ in placed)
    # the warm-up forwards lie before the window: gone
    assert sorted(s[5]["batch"] for s in placed
                  if s[0] == "cnn.forward") == [2, 3, 4, 5, 6, 7]
    gc_spans = [s for s in placed if s[0] == "py.gc"]
    assert len(gc_spans) == 1 and gc_spans[0][2] == hi
    assert abs(gc_spans[0][1] - 98 * MS) <= 1 * US
    assert [s[3] for s in placed if s[0] == "cnn.retrace"] == [102]
    assert [s[1] for s in placed] == sorted(s[1] for s in placed)


def test_idle_inside_the_copy_over_two_devices():
    """Device 0 idles 30-50 ms, device 1 40-60 ms; the host copies
    35-45 and 55-58 ms: 10 + (5 + 3) ms idle inside copies, over two
    devices and a 100 ms window, is 9 %."""
    devices = {"/device:TPU:0": [Op("fused_conv", 0, 30 * MS, True),
                                 Op("fc", 50 * MS, 100 * MS, True)],
               "/device:TPU:1": [Op("fused_conv", 0, 40 * MS, True),
                                 Op("fc", 60 * MS, 100 * MS, True)]}
    red = _trace(devices)
    placed = [("cnn.h2d", 35 * MS, 45 * MS, 1, 0, {}),
              ("cnn.h2d", 55 * MS, 58 * MS, 2, 0, {}),
              ("cnn.dispatch", 45 * MS, 50 * MS, 3, 0, {})]
    assert program_spans.idle_overlap_share(red, placed, "cnn.h2d") == \
        pytest.approx(0.09)
    assert program_spans.idle_overlap_share(red, placed, "py.gc") == 0.0
    assert program_spans.idle_overlap_share(_trace(), placed,
                                            "cnn.h2d") is None


def test_no_program_spans_gives_none(monkeypatch):
    red = _trace()
    assert program_spans.place(red, []) is None
    assert program_spans.place(red, None) is None
    # spans, but no forward to align by
    assert program_spans.place(red, [("py.gc", 1, 2, 1, 0, {})]) is None
    # a program without the span log (its module lacks SPANS), or none
    monkeypatch.setitem(sys.modules, program_spans.PROGRAM_MODULE,
                        SimpleNamespace())
    assert program_spans.recorded() is None
    monkeypatch.delitem(sys.modules, program_spans.PROGRAM_MODULE)
    assert program_spans.recorded() is None
    ctx = SimpleNamespace(trace=red)
    for name in ("h2d_ms.offline", "h2d_ms.stream", "dispatch_ms.stream",
                 "idle_in_h2d.offline", "gc_ms.stream"):
        assert harness.load_module("metrics", name).read(ctx) is None
    assert harness.load_module("metrics", "h2d_ms.offline").read(
        SimpleNamespace(trace=None)) is None


def test_metrics_read_the_program_log(monkeypatch):
    log = SimpleNamespace(read=lambda: {"spans": _program(),
                                        "counters": {}, "dropped": 0})
    monkeypatch.setitem(sys.modules, program_spans.PROGRAM_MODULE,
                        SimpleNamespace(SPANS=log))
    devices = {"/device:TPU:0": [Op("fused_conv", 0, 100 * MS, True)]}
    ctx = SimpleNamespace(trace=_trace(devices))
    read = lambda name: harness.load_module("metrics", name).read(ctx)
    assert read("h2d_ms.offline") == pytest.approx(0.395)
    assert read("h2d_ms.stream") == pytest.approx(0.395)
    assert read("dispatch_ms.stream") == pytest.approx(0.5)
    assert read("gc_ms.stream") == 0.0          # kept spans, no collection
    assert read("idle_in_h2d.offline") == 0.0   # the device never idles
