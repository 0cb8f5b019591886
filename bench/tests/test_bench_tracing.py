"""The trace reduction, on a small synthetic trace."""
import pytest

from bench import tracing
from bench.tracing import Op

MS = 1_000_000
KERNEL = 'custom_call_target="tpu_custom_call"'


def _op(name, start_ms, end_ms):
    return Op(tracing.family(name), int(start_ms * MS), int(end_ms * MS),
              KERNEL in name)


def _trace():
    """A 10 ms window on two chips. Chip 0: a conv kernel 1-4 ms, an
    overlapping copy 3-5 ms, an FC kernel 7-8 ms: busy 5 ms. Chip 1: the
    conv kernel 2-4 ms: busy 2 ms. The host batches 0-1 ms, dispatches
    1-2 ms, fetches 2-9 ms."""
    conv = (f'%fused_conv.6 = f32[2,32,27,27,128] custom-call(f32[2] %pad.43)'
            f', {KERNEL}, frontend_attributes={{}}')
    fc = f'%fc.3 = f32[32,4096] custom-call(f32[32,9216] %r), {KERNEL}'
    copy = '%copy.24 = f32[1,32,227,227,3] copy(f32[1,32,227,227,3] %b)'
    devices = {
        "/device:TPU:0": [_op(conv, 1, 4), _op(copy, 3, 5), _op(fc, 7, 8),
                          _op(conv, 11, 12)],          # after the window
        "/device:TPU:1": [_op(conv, 2, 4)],
    }
    spans = [("bench.window", 0, 10 * MS), ("bench.batch", 0, 1 * MS),
             ("bench.forward", 1 * MS, 2 * MS),
             ("bench.fetch", 2 * MS, 9 * MS)]
    return tracing.reduce(devices, spans)


def test_family_strips_the_instance_number():
    assert tracing.family('%fused_conv.6 = f32[2] custom-call()') == \
        "fused_conv"
    assert tracing.family("%reduce_window.10.clone = f32[8]") == \
        "reduce_window"
    assert tracing.family("copy.24") == "copy"
    assert tracing.family("fusion") == "fusion"


def test_union_merges_overlaps():
    assert tracing.union([(3, 5), (1, 4), (7, 8), (8, 9)]) == \
        [(1, 5), (7, 9)]


def test_idle_share_is_one_minus_the_union_over_the_window():
    red = _trace()
    assert red.window_s == pytest.approx(0.010)
    # chip 0 busy 5 ms (overlap counted once), chip 1 busy 2 ms
    assert tracing.busy_s(red) == pytest.approx(0.0035)
    assert tracing.idle_share(red) == pytest.approx(1 - 3.5 / 10)


def test_ops_are_clipped_to_the_window():
    red = _trace()
    assert all(o.end <= 10 * MS for ops in red.devices.values()
               for o in ops)
    assert len(red.devices["/device:TPU:0"]) == 3


def test_kernels_are_classified_by_name():
    red = _trace()
    conv = tracing.kernel_ops(red, ("fused_conv",))
    assert len(conv) == 2                     # one on each chip
    assert sum(o.end - o.start for o in conv) == 5 * MS
    assert len(tracing.kernel_ops(red, ("fc",))) == 1
    # a plain XLA op is no kernel, whatever its name
    assert tracing.kernel_ops(red, ("copy",)) == []
    top = dict(tracing.top_ops(red))
    assert top == pytest.approx({"fused_conv": 0.005, "copy": 0.002,
                                 "fc": 0.001})


def test_idle_gaps_are_attributed_to_the_host_span():
    red = _trace()
    # chip 0 idles 0-1 (batch), 5-7 and 8-10 (fetch); chip 1 idles 0-2
    # (batch 1 ms, forward 1 ms: a tie, either) and 4-10 (fetch)
    gaps = dict(tracing.idle_by_host(red))
    assert gaps["bench.fetch"] == pytest.approx((4 + 6) / 2 * 1e-3)
    assert sum(gaps.values()) == pytest.approx(
        tracing.idle_share(red) * red.window_s)
    assert tracing.host_activity(red, 0, MS // 2) == "bench.batch"
    assert tracing.host_activity(red, 9 * MS + 1, 10 * MS) == \
        tracing.NO_SPAN_NAME


def test_clock_markers_place_the_host_spans():
    # the device starts each marker 1000 ns into its host span; host and
    # trace clocks differ by 5e9 ns
    host = [("bench.clock", 100, 4100), ("bench.clock", 5000, 9000),
            ("bench.window", 10_000, 20_000)]
    markers = [5_000_000_000 + 1100, 5_000_000_000 + 6000]
    shift, err = tracing.clock_shift(markers, host)
    assert shift == pytest.approx(5e9 - 1000)
    assert err == 2000
    with pytest.raises(ValueError):
        tracing.clock_shift(markers[:1], host)
