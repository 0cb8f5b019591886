"""Wall-clock spans of real runs (``repro.obs.SPANS``): the span log's
buffer, switch, garbage-collector hook and Chrome export, and the
``cnn.*`` spans and retrace counter of ``CompiledCNN.forward``."""
import gc
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.config import CNNConfig, ConvLayer
from repro.obs import SPANS, SpanLog, set_spans, validate_trace
from repro.pipeline import ExecutionSpec, Serving, compile_cnn
from tests.test_parallel import run_in_mesh_subprocess

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


@pytest.fixture(scope="module")
def compiled():
    cfg = get_config("alexnet").smoke()
    c = compile_cnn(cfg, ExecutionSpec(serving=Serving(batch=2)),
                    key=jax.random.key(1), with_engine=False)
    return c, cfg


def _images(cfg, n, seed=0):
    return np.random.default_rng(seed).standard_normal(
        (n, cfg.input_hw, cfg.input_hw, cfg.input_ch)).astype(np.float32)


@pytest.fixture
def spans():
    """The process's span log, emptied, and switched back on after."""
    was = set_spans(True)
    SPANS.clear()
    yield SPANS
    set_spans(was)
    SPANS.clear()


def _named(log, name):
    return [s for s in log.read()["spans"] if s[0] == name]


def test_forward_spans_nest_under_one_batch(compiled, spans):
    c, cfg = compiled
    x = _images(cfg, 2)
    for _ in range(2):
        np.asarray(c.forward(x))
    fwd = _named(spans, "cnn.forward")
    h2d = _named(spans, "cnn.h2d")
    disp = _named(spans, "cnn.dispatch")
    assert len(fwd) == len(h2d) == len(disp) == 2
    batches = [f[5]["batch"] for f in fwd]
    assert batches[1] == batches[0] + 1
    for f, h, d in zip(fwd, h2d, disp):
        name, t0, t1, sid, parent, args = f
        assert parent == 0 and sid > 0
        assert args["images"] == 2 and args["bytes"] == x.nbytes
        assert h[4] == d[4] == sid                 # children of the forward
        assert h[5] == {"bytes": x.nbytes, "devices": 1}
        assert d[5] is None
        assert t0 <= h[1] <= h[2] <= d[1] <= d[2] <= t1


def test_spans_change_no_logits(compiled, spans):
    c, cfg = compiled
    x = _images(cfg, 2, seed=3)
    on = np.asarray(c.forward(x))
    set_spans(False)
    off = np.asarray(c.forward(x))
    np.testing.assert_array_equal(on, off)


def test_buffer_is_bounded_and_counts_what_it_dropped():
    log = SpanLog(capacity=4)
    ids = [log.record(f"s{i}", i, i + 1) for i in range(10)]
    got = log.read()
    assert [s[0] for s in got["spans"]] == ["s6", "s7", "s8", "s9"]
    assert [s[3] for s in got["spans"]] == ids[6:]
    assert got["dropped"] == 6
    log.clear()
    assert log.read() == {"spans": [], "counters": {}, "dropped": 0}


def test_kept_spans_leave_the_collectors_books():
    """Flat args keep a span's tuple free of containers, so the
    collector stops tracking it (within two passes) and a full buffer
    adds no work to later collections."""
    log = SpanLog(capacity=64)
    for i in range(100):
        log.record("s", i, i + 1, 0, ("k", i, "shape", (8, 3)))
    gc.collect()
    gc.collect()
    assert not any(gc.is_tracked(s) for s in log._spans)
    assert log.read()["spans"][-1][5] == {"k": 99, "shape": (8, 3)}


def test_read_survives_collections_while_it_copies(spans):
    """A collection during ``read`` records a ``py.gc`` span into the
    buffer being read; the read-out must not trip over it."""
    for i in range(5000):
        spans.record("s", i, i + 1, 0, ("k", i))
    was = gc.get_threshold()
    gc.set_threshold(1, 1, 1)
    try:
        got = spans.read()
    finally:
        gc.set_threshold(*was)
    assert sum(s[0] == "s" for s in got["spans"]) == 5000


def test_off_records_nothing(compiled, spans):
    log = SpanLog(enabled=False)
    assert log.record("a", 1, 2) == 0
    assert log.instant("b") == 0
    log.count("d")
    assert log.read() == {"spans": [], "counters": {}, "dropped": 0}

    c, cfg = compiled
    set_spans(False)
    np.asarray(c.forward(_images(cfg, 2)))
    gc.collect()
    assert spans.read() == {"spans": [], "counters": {}, "dropped": 0}


def test_env_switch_read_at_import():
    code = ("import gc; from repro.obs import SPANS; from repro.obs.trace "
            "import _on_gc; print(SPANS.enabled, _on_gc in gc.callbacks)")
    out = {}
    for value in ("0", "1"):
        env = dict(os.environ, REPRO_SPANS=value, JAX_PLATFORMS="cpu",
                   PYTHONPATH=os.path.abspath(SRC))
        out[value] = subprocess.run(
            [sys.executable, "-c", code], env=env, capture_output=True,
            text=True, timeout=120, check=True).stdout.split()
    assert out == {"0": ["False", "False"], "1": ["True", "True"]}


def test_forced_collection_gives_one_gen2_span(spans):
    gc.collect()
    gen2 = [s for s in _named(spans, "py.gc") if s[5]["generation"] == 2]
    assert len(gen2) == 1
    name, t0, t1, sid, parent, args = gen2[0]
    assert t1 >= t0 and args["collected"] >= 0
    assert all(s[5]["generation"] in (1, 2)
               for s in _named(spans, "py.gc"))


def test_retrace_counts_each_new_shape_once(compiled, spans):
    c, cfg = compiled
    traces = lambda: spans.read()["counters"].get("cnn.retrace", 0)
    x3 = _images(cfg, 3)                 # a batch no other test traced
    np.asarray(c.forward(x3))
    assert traces() == 1
    np.asarray(c.forward(x3))
    assert traces() == 1                 # a repeat call is not a trace
    c.lower(_images(cfg, 5))
    assert traces() == 2                 # lower() traces a new shape too
    np.asarray(c.forward(_images(cfg, 5)))
    assert traces() == 2                 # ... and the call reuses it
    marks = _named(spans, "cnn.retrace")
    assert [m[5]["shape"][0] for m in marks] == [3, 5]
    assert all(m[1] == m[2] for m in marks)          # instants
    assert marks[0][5] == {"shape": x3.shape, "dtype": "float32",
                           "placement": "single"}


def test_kw_fold_counted_per_folded_conv_group(spans):
    """``compile_cnn`` counts ``conv.kw_fold`` once for each conv group
    whose column taps ``conv_pipe`` folds: AlexNet's conv1 and conv2,
    and none in a net whose conv channels fill a lane tile."""
    from repro.models.cnn import init_cnn_params

    cfg = get_config("alexnet")
    params = jax.eval_shape(lambda: init_cnn_params(jax.random.key(0), cfg))
    compile_cnn(cfg, ExecutionSpec(serving=Serving(batch=8)), params,
                with_engine=False)
    assert spans.read()["counters"].get("conv.kw_fold") == 2
    spans.clear()
    wide = CNNConfig(name="wide", input_hw=9, input_ch=128, n_classes=4,
                     layers=(ConvLayer("conv", out_ch=128, kernel=3, pad=1),
                             ConvLayer("fc", out_ch=4, relu=False)))
    compile_cnn(wide, ExecutionSpec(serving=Serving(batch=2)),
                key=jax.random.key(0), with_engine=False)
    assert "conv.kw_fold" not in spans.read()["counters"]


@pytest.mark.parametrize("arch,folded", [("alexnet", 1), ("vgg16", 3)])
def test_kh_fold_counted_per_row_folded_conv_group(spans, arch, folded):
    """``compile_cnn`` counts ``conv.kh_fold`` once for each conv group
    whose row taps fold too: AlexNet's conv1; VGG-16's conv1_1, conv1_2
    and conv2_1; none in a net whose conv channels fill a lane tile."""
    from repro.models.cnn import init_cnn_params

    cfg = get_config(arch)
    params = jax.eval_shape(lambda: init_cnn_params(jax.random.key(0), cfg))
    compile_cnn(cfg, ExecutionSpec(serving=Serving(batch=8)), params,
                with_engine=False)
    assert spans.read()["counters"].get("conv.kh_fold") == folded
    spans.clear()
    wide = CNNConfig(name="wide", input_hw=9, input_ch=128, n_classes=4,
                     layers=(ConvLayer("conv", out_ch=128, kernel=3, pad=1),
                             ConvLayer("fc", out_ch=4, relu=False)))
    compile_cnn(wide, ExecutionSpec(serving=Serving(batch=2)),
                key=jax.random.key(0), with_engine=False)
    assert "conv.kh_fold" not in spans.read()["counters"]


@pytest.mark.parametrize("arch,pooled", [("alexnet", 1), ("vgg16", 5)])
def test_pool_fused_counted_per_pooled_conv_group(spans, arch, pooled):
    """``compile_cnn`` counts ``conv.pool_fused`` once for each conv group
    whose pool runs in ``conv_pipe``'s epilogue: AlexNet's conv5 (its
    other pools follow an LRN), every VGG-16 block's last conv."""
    from repro.models.cnn import init_cnn_params

    cfg = get_config(arch).smoke()
    params = jax.eval_shape(lambda: init_cnn_params(jax.random.key(0), cfg))
    compile_cnn(cfg, ExecutionSpec(serving=Serving(batch=2)), params,
                with_engine=False)
    assert spans.read()["counters"].get("conv.pool_fused") == pooled


@pytest.mark.parametrize("arch,halo", [("alexnet", 4), ("vgg16", 8)])
def test_halo_in_kernel_counted_per_conv_group(spans, arch, halo):
    """``compile_cnn`` counts ``conv.halo_in_kernel`` once for each conv
    group whose zero halo ``conv_pipe`` makes in VMEM: AlexNet conv2-5
    (conv1 is strided, its space-to-depth copy holds the halo); VGG-16's
    groups whose plan still fits the budget with the line buffer, all
    but conv3_1, conv3_2 and conv4_1-4_3, which keep the wrapper's pad."""
    from repro.models.cnn import init_cnn_params

    cfg = get_config(arch)
    params = jax.eval_shape(lambda: init_cnn_params(jax.random.key(0), cfg))
    compile_cnn(cfg, ExecutionSpec(serving=Serving(batch=8)), params,
                with_engine=False)
    assert spans.read()["counters"].get("conv.halo_in_kernel") == halo


def test_chrome_export_validates(compiled, spans):
    c, cfg = compiled
    np.asarray(c.forward(_images(cfg, 4)))         # traced: one instant
    gc.collect()
    rec = spans.export()
    doc = rec.to_chrome()
    assert validate_trace(doc) == []
    evs = [e for e in doc["traceEvents"] if e["ph"] != "M"]
    names = {e["name"]: e["ph"] for e in evs}
    assert names["cnn.forward"] == names["cnn.h2d"] == "X"
    assert names["cnn.retrace"] == "i" and names["py.gc"] == "X"
    assert {e["cat"] for e in evs} == {"wall"}
    assert min(e["ts"] for e in evs) == 0.0
    assert doc["otherData"]["wall_counters"]["cnn.retrace"] == 1
    fwd = next(e for e in evs if e["name"] == "cnn.forward")
    h2d = next(e for e in evs if e["name"] == "cnn.h2d")
    assert h2d["args"]["parent"] == fwd["args"]["id"]


def test_dp_and_pp_forward_spans_on_4_devices():
    """The same spans under dp (the batch copied to four chips) and pp."""
    run_in_mesh_subprocess("""
        from repro.configs import get_config
        from repro.obs import SPANS
        from repro.pipeline import (ExecutionSpec, Placement, Serving,
                                    compile_cnn)

        cfg = get_config('alexnet').smoke()
        x = np.zeros((8, cfg.input_hw, cfg.input_hw, cfg.input_ch),
                     np.float32)
        for placement, batch, devices in (
                (Placement(replicas=4), 2, 4),
                (Placement(pp_stages=2, microbatches=2), 8, 1)):
            c = compile_cnn(cfg, ExecutionSpec(
                placement=placement, use_pallas=False,
                serving=Serving(batch=batch, clock='modeled')),
                key=jax.random.key(0))
            SPANS.clear()
            np.asarray(c.forward(x))
            got = {s[0]: s for s in SPANS.read()['spans']}
            assert got['cnn.h2d'][5]['devices'] == devices, got
            assert got['cnn.h2d'][4] == got['cnn.forward'][3]
            assert got['cnn.retrace'][5]['shape'] == x.shape
            assert got['cnn.retrace'][5]['placement'] == c.mode
    """)
