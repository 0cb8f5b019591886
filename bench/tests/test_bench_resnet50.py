"""The ``resnet50`` configuration: its layers are the program's and the
published ones, its control fails its limit, its cell, shrunk to the
program's smoke size, runs correct through the harness on the CPU, and
``glue_share.offline`` reads a trace as it says."""
import dataclasses
import json
import os
import sys
import time

import jax
import pytest

from bench import correct, harness, tracing
from bench.counts import flops_per_image
from bench.references import cnn_float
from bench.tests.test_bench_counts import _resnet50
from bench.tests.test_bench_tracing import KERNEL, MS, _op
from bench.weights import make_images, make_weights

SRC = os.path.join(harness.ROOT, "src")
if SRC not in sys.path:
    sys.path.insert(0, SRC)

SEED = 2 ** 31 + 11


def _config():
    with open(os.path.join(harness.ROOT, "bench", "configs",
                           "resnet50.json")) as f:
        return json.load(f)


def _smoke(cfg):
    """``cfg`` over the program's ResNet-50 smoke configuration (same
    graph, widths / 16, 67 x 67 input), and that configuration."""
    from repro import configs
    from bench.systems.compiled_cnn import _program_layers

    small = configs.get_config("resnet50").smoke()
    return dict(cfg, layers=_program_layers(small),
                input={"hw": small.input_hw, "ch": small.input_ch},
                n_classes=small.n_classes), small


def test_config_holds_the_program_and_published_layers():
    from repro.configs import get_config
    from bench.systems.compiled_cnn import _program_layers

    cfg = _config()
    assert cfg["layers"] == _resnet50()["layers"]
    assert cfg["layers"] == _program_layers(get_config("resnet50"))
    assert cfg["input"] == _resnet50()["input"]
    assert flops_per_image(cfg) == 8_178_368_512


@pytest.mark.parametrize("passes", cnn_float.CONTROLS[:1])
def test_control_fails_the_limit_at_a_small_width(passes):
    """bfloat16 three passes in the program's place, on the smoke
    graph, two images: the limit must refuse it."""
    cfg, _ = _smoke(_config())
    weights = make_weights(cfg, SEED)
    x = make_images(cfg, SEED, 2)
    want = cnn_float.logits(cfg, weights, x, block=2)
    low = cnn_float.logits(cfg, weights, x, passes=passes, block=2)
    err = correct.logit_rel_err(low, want)
    ok, _ = correct.judge({"logit_rel_err": err}, cfg["limits"])
    assert not ok, err


def test_smoke_cell_is_correct(monkeypatch):
    """``resnet50-offline`` with the program's smoke configuration in
    place of its own: the residual epilogues, the subsampled 1x1
    projections, the padded pool and gap, through ``compile_cnn``."""
    from repro import configs

    cell = harness.resolve(harness.load_benchmark(), "resnet50-offline")
    cfg, small = _smoke(cell.cfg)
    monkeypatch.setattr(configs, "get_config", lambda name: small)
    monkeypatch.setattr(harness, "enable_compile_cache", lambda: "off")
    traffic = dict(cell.traffic, batch_per_chip=2, check_batches=2,
                   pool_batches=2, in_flight=1)
    cell = dataclasses.replace(cell, cfg=cfg, traffic=traffic)
    result = harness.run(cell, SEED, 0.2, False, time.perf_counter(),
                         devices=jax.devices())
    assert result["correct"] is True, result["compared"]["logit_rel_err"]
    assert result["attempted"] > 0 and result["failed"] == 0


def test_glue_share_is_the_time_outside_kernels_over_all_op_time():
    """Chip 0: a conv kernel 1-4 ms, a pad 3-5 ms, an add 6-7 ms (glue 3
    of 6 ms: 50 %); chip 1: the conv kernel 2-4 ms, a copy 4-6 ms (50 %
    too, 2 of 4 ms). Overlap is not merged: each op's time counts."""
    conv = f"%fused_conv.6 = f32[2,7,7,64] custom-call(f32[2] %x), {KERNEL}"
    red = tracing.reduce(
        {"/device:TPU:0": [_op(conv, 1, 4), _op("%pad.3 = f32[2]", 3, 5),
                           _op("%add.1 = f32[2]", 6, 7)],
         "/device:TPU:1": [_op(conv, 2, 4), _op("%copy.2 = f32[2]", 4, 6)]},
        [("bench.window", 0, 10 * MS)])
    ctx = harness.Context(cell=None, window=None, trace=red,
                          device_kind="TPU v5 lite", chips=2)
    glue = harness.load_module("metrics", "glue_share.offline")
    assert glue.read(ctx) == pytest.approx(50.0)
    red.devices["/device:TPU:1"] = [_op(conv, 2, 4)]
    assert glue.read(ctx) == pytest.approx(25.0)
    assert glue.read(dataclasses.replace(ctx, trace=None)) is None
