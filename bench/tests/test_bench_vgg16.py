"""The ``vgg16`` configuration: its layers are the published ones, its
control fails its limit at full width, and its cell, shrunk to the
program's smoke size, runs correct through the harness on the CPU."""
import dataclasses
import json
import os
import sys
import time

import jax
import pytest

from bench import correct, harness
from bench.counts import flops_per_image
from bench.references import cnn_float
from bench.tests.test_bench_counts import _vgg16
from bench.weights import make_images, make_weights

SRC = os.path.join(harness.ROOT, "src")
if SRC not in sys.path:
    sys.path.insert(0, SRC)


def _config():
    with open(os.path.join(harness.ROOT, "bench", "configs",
                           "vgg16.json")) as f:
        return json.load(f)


def test_config_holds_the_published_layers():
    cfg = _config()
    assert cfg["layers"] == _vgg16()["layers"]
    assert cfg["input"] == _vgg16()["input"]
    assert flops_per_image(cfg) == 30_940_528_640


@pytest.mark.parametrize("passes", cnn_float.CONTROLS[:1])
def test_control_fails_the_limit_at_full_width(passes):
    """bfloat16 three passes in the program's place, at VGG-16's
    published widths, one image: the limit must refuse it."""
    cfg = _config()
    weights = make_weights(cfg, 2 ** 31 + 3)
    x = make_images(cfg, 2 ** 31 + 3, 1)
    want = cnn_float.logits(cfg, weights, x, block=1)
    low = cnn_float.logits(cfg, weights, x, passes=passes, block=1)
    err = correct.logit_rel_err(low, want)
    ok, _ = correct.judge({"logit_rel_err": err}, cfg["limits"])
    assert not ok, err


def test_smoke_cell_is_correct(monkeypatch):
    """``vgg16-offline`` with the program's VGG-16 smoke configuration
    (same topology, narrow channels) in place of its own."""
    from repro import configs
    from bench.systems.compiled_cnn import _program_layers

    small = configs.get_config("vgg16").smoke()
    monkeypatch.setattr(configs, "get_config", lambda name: small)
    monkeypatch.setattr(harness, "enable_compile_cache", lambda: "off")
    cell = harness.resolve(harness.load_benchmark(), "vgg16-offline")
    cfg = dict(cell.cfg, layers=_program_layers(small),
               input={"hw": small.input_hw, "ch": small.input_ch},
               n_classes=small.n_classes)
    traffic = dict(cell.traffic, batch_per_chip=4, check_batches=3,
                   pool_images=16)
    cell = dataclasses.replace(cell, cfg=cfg, traffic=traffic)
    result = harness.run(cell, 2 ** 31 + 7, 0.4, False, time.perf_counter(),
                         devices=jax.devices())
    assert result["correct"] is True, result["compared"]["logit_rel_err"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert list(result)[-1] == "compared"
