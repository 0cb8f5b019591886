"""The comparison that decides ``correct``: its control fails it, the
program at a small size passes it, and a broken timed path fails it."""
import dataclasses
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import correct, harness
from bench.references import cnn_float
from bench.weights import make_images, make_weights

SRC = os.path.join(harness.ROOT, "src")
if SRC not in sys.path:
    sys.path.insert(0, SRC)


def _config(name):
    with open(os.path.join(harness.ROOT, "bench", "configs",
                           name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("passes", cnn_float.CONTROLS[:1])
def test_control_fails_the_limit_at_full_width(passes):
    """bfloat16 three passes in the program's place, at AlexNet's
    published widths, two images: the limit must refuse it."""
    cfg = _config("alexnet")
    weights = make_weights(cfg, 2 ** 31 + 3)
    x = make_images(cfg, 2 ** 31 + 3, 2)
    want = cnn_float.logits(cfg, weights, x, block=2)
    low = cnn_float.logits(cfg, weights, x, passes=passes, block=2)
    err = correct.logit_rel_err(low, want)
    ok, _ = correct.judge({"logit_rel_err": err}, cfg["limits"])
    assert not ok, err


def test_pwl_table_matches_the_exact_power_within_half_a_percent():
    lrn = _config("alexnet")["lrn"]
    slope, icpt = cnn_float.pwl_table(lrn["beta"], lrn["sub_bits"],
                                      lrn["z_exp"], lrn["fit_points"])
    assert len(slope) == (lrn["z_exp"][1] - lrn["z_exp"][0]) * 4
    z = np.linspace(1.0, 2.0 ** 16 - 1, 200_001)
    seg = np.floor(np.log2(z)).astype(int) * 4 + np.floor(
        (z / 2.0 ** np.floor(np.log2(z)) - 1) * 4).astype(int)
    approx = slope[seg] * z + icpt[seg]
    assert np.max(np.abs(approx / z ** -lrn["beta"] - 1)) < 5e-3


# -- whole runs at a small size on the CPU, faults planted underneath --

def _smoke_cell(monkeypatch, workload, **traffic):
    from repro import configs
    from bench.systems.compiled_cnn import _program_layers

    small = configs.get_config("alexnet").smoke()
    monkeypatch.setattr(configs, "get_config", lambda name: small)
    monkeypatch.setattr(harness, "enable_compile_cache", lambda: "off")
    cell = harness.resolve(harness.load_benchmark(), workload)
    cfg = dict(cell.cfg, layers=_program_layers(small),
               input={"hw": small.input_hw, "ch": small.input_ch},
               n_classes=small.n_classes)
    return dataclasses.replace(cell, cfg=cfg,
                               traffic=dict(cell.traffic, **traffic))


class _Faulty:
    """The system under test with one fault planted in its outputs."""

    def __init__(self, system, fault):
        self.system, self.fault = system, fault
        self.batch = system.batch
        self.prev = None

    def forward(self, x):
        out = self.system.forward(x)
        n = out.shape[0]
        if self.fault == "stale":          # returns the last call's answer
            out, self.prev = (self.prev if self.prev is not None
                              else out), out
        elif self.fault == "half_batch":   # half the batch never computed
            out = jnp.concatenate([out[:n // 2], out[:n - n // 2]])
        elif self.fault == "one_shard":    # one replica's rows everywhere
            q = max(1, n // 4)
            out = jnp.tile(out[:q], (n // q, 1))
        elif self.fault == "altered":      # one answer nudged where made
            top = jnp.argmax(out[0])
            out = out.at[0, top].multiply(1.0 + 1e-3)
        return out

    def close(self):
        self.system.close()


@pytest.mark.parametrize("workload,fault", [
    ("alexnet-offline", None), ("alexnet-stream", None),
    ("alexnet-offline", "stale"), ("alexnet-offline", "half_batch"),
    ("alexnet-offline", "one_shard"), ("alexnet-stream", "altered")])
def test_a_run_is_correct_only_with_the_timed_path_sound(
        monkeypatch, workload, fault):
    traffic = dict(batch_per_chip=4, check_batches=3, pool_images=16,
                   check_requests=8, rate_per_s=40)
    cell = _smoke_cell(monkeypatch, workload, **traffic)
    wrap = None if fault is None else (lambda s: _Faulty(s, fault))
    result = harness.run(cell, 2 ** 31 + 7, 0.4, False, time.perf_counter(),
                         devices=jax.devices(), wrap_system=wrap)
    err = result["compared"]["logit_rel_err"]
    assert result["correct"] is (fault is None), err
    assert result["attempted"] > 0 and result["failed"] == 0
    assert list(result)[-1] == "compared"
