"""The configuration's layer graph (``in``, ``add``, ``gap``, a padded
pool) through the weights, the reference and the program's layer map;
and a chain still gives the weights and counts it always gave."""
import json
import os
import sys
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import correct, harness
from bench.counts import layer_costs
from bench.references import cnn_float
from bench.systems.compiled_cnn import _program_layers
from bench.tests.test_bench_counts import _resnet50
from bench.weights import make_images, make_weights, param_shapes

SRC = os.path.join(harness.ROOT, "src")
if SRC not in sys.path:
    sys.path.insert(0, SRC)


def _config(name):
    with open(os.path.join(harness.ROOT, "bench", "configs",
                           name + ".json")) as f:
        return json.load(f)


# -- a chain gives the counts and weights it always gave --

def _small(name, hw):
    """``name``'s layer list with an ``hw`` input, convs an eighth as
    wide, FC layers 64 wide and 10 classes."""
    cfg = _config(name)
    fcs = [i for i, l in enumerate(cfg["layers"]) if l["kind"] == "fc"]
    layers = []
    for i, l in enumerate(cfg["layers"]):
        l = dict(l)
        if l["kind"] == "conv":
            l["out"] //= 8
        elif l["kind"] == "fc":
            l["out"] = 10 if i == fcs[-1] else 64
        layers.append(l)
    return dict(cfg, input={"hw": hw, "ch": 3}, layers=layers)


# (out_shape, flops, weight_elems, act_elems) per layer, and the sum and
# sum of squares of every weight and bias drawn from seed 2**33 + 5
CHAINS = {
    ("alexnet", 67): ([
        ((15, 15, 12), 1960200, 4368, 16167),
        ((15, 15, 12), 0, 0, 5400),
        ((7, 7, 12), 0, 0, 3288),
        ((7, 7, 32), 470400, 4832, 2156),
        ((7, 7, 32), 0, 0, 3136),
        ((3, 3, 32), 0, 0, 1856),
        ((3, 3, 48), 248832, 13872, 720),
        ((3, 3, 48), 186624, 10416, 864),
        ((3, 3, 32), 124416, 6944, 720),
        ((1, 1, 32), 0, 0, 320),
        ((1, 1, 64), 4096, 2112, 96),
        ((1, 1, 64), 8192, 4160, 128),
        ((1, 1, 10), 1280, 650, 74)],
        -10.82485417422231, 614.7098031209968),
    ("vgg16", 32): ([
        ((32, 32, 8), 442368, 224, 11264),
        ((32, 32, 8), 1179648, 584, 16384),
        ((16, 16, 8), 0, 0, 10240),
        ((16, 16, 16), 589824, 1168, 6144),
        ((16, 16, 16), 1179648, 2320, 8192),
        ((8, 8, 16), 0, 0, 5120),
        ((8, 8, 32), 589824, 4640, 3072),
        ((8, 8, 32), 1179648, 9248, 4096),
        ((8, 8, 32), 1179648, 9248, 4096),
        ((4, 4, 32), 0, 0, 2560),
        ((4, 4, 64), 589824, 18496, 1536),
        ((4, 4, 64), 1179648, 36928, 2048),
        ((4, 4, 64), 1179648, 36928, 2048),
        ((2, 2, 64), 0, 0, 1280),
        ((2, 2, 64), 294912, 36928, 512),
        ((2, 2, 64), 294912, 36928, 512),
        ((2, 2, 64), 294912, 36928, 512),
        ((1, 1, 64), 0, 0, 320),
        ((1, 1, 64), 8192, 4160, 128),
        ((1, 1, 64), 8192, 4160, 128),
        ((1, 1, 10), 1280, 650, 74)],
        -7.500694207351756, 1327.4446979210984),
}


@pytest.mark.parametrize("name,hw", sorted(CHAINS))
def test_chain_costs_and_weights_pinned(name, hw):
    """A chain reads each layer's input from the layer before it, and
    its weights split their keys over the conv and FC layers alone. The
    sums catch any key that moves to another leaf (a moved key shifts
    them by O(1)); the tolerance leaves room for the last bits of the
    CPU's normal draw, which this test does not pin."""
    cfg = _small(name, hw)
    rows, total, squares = CHAINS[(name, hw)]
    costs = layer_costs(cfg)
    assert [(c.out_shape, c.flops, c.weight_elems, c.act_elems)
            for c in costs] == rows
    assert all(c.in_shape == p.out_shape for p, c in zip(costs, costs[1:]))
    weights = make_weights(cfg, 2 ** 33 + 5)
    leaves = [np.asarray(x, np.float64) for p in weights if p is not None
              for x in (p["w"], p["b"])]
    assert [p is None for p in weights] == [
        l["kind"] not in ("conv", "fc") for l in cfg["layers"]]
    assert sum(float(a.sum()) for a in leaves) == pytest.approx(
        total, abs=1e-3)
    assert sum(float((a * a).sum()) for a in leaves) == pytest.approx(
        squares, rel=1e-6)


def test_resnet50_weights_follow_the_graph():
    cfg = _resnet50()
    shapes = param_shapes(cfg)
    kinds = [l["kind"] for l in cfg["layers"]]
    assert [s is None for s in shapes] == [k not in ("conv", "fc")
                                           for k in kinds]
    # weights + biases of every conv and the FC
    assert sum(int(np.prod(s[0])) + s[0][-1] for s in shapes
               if s is not None) == 25_530_472
    # the first projection reads the pool's 64 channels, not conv 4's 256
    assert shapes[5][0] == (1, 1, 64, 256)


# -- a tiny residual graph against a forward written out by hand --

def _conv(m, k, s=1, relu=True, **kw):
    return dict({"kind": "conv", "out": m, "k": k, "stride": s,
                 "pad": k // 2, "groups": 1, "relu": relu}, **kw)


def _tiny():
    """A stem, a padded max pool, a projection block at stride 2, an
    identity block, gap and FC: ResNet-50's parts at 16 x 16 pixels."""
    return {
        "input": {"hw": 16, "ch": 3}, "precision": {"dtype": "float32"},
        "layers": [
            _conv(8, 3, **{"in": [-1]}),                               # 0
            {"kind": "pool", "op": "max", "k": 3, "stride": 2, "pad": 1},
            _conv(8, 1), _conv(8, 3, 2), _conv(32, 1, relu=False),    # 2-4
            _conv(32, 1, 2, relu=False, **{"in": [1]}),                # 5
            {"kind": "add", "in": [4, 5], "relu": True},               # 6
            _conv(8, 1), _conv(8, 3), _conv(32, 1, relu=False),       # 7-9
            {"kind": "add", "in": [9, 6], "relu": True},               # 10
            {"kind": "gap"},
            {"kind": "fc", "out": 10, "relu": False}]}


def _by_hand(w, x, shortcut=True):
    """``_tiny`` in ``jax.lax``, layer by layer, at HIGHEST."""
    hi = jax.lax.Precision.HIGHEST

    def conv(x, p, s, pad, relu):
        y = jax.lax.conv_general_dilated(
            x, p["w"], (s, s), [(pad, pad), (pad, pad)],
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=hi) \
            + p["b"]
        return jnp.maximum(y, 0.0) if relu else y

    stem = conv(x, w[0], 1, 1, True)
    pool = jax.lax.reduce_window(stem, -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                                 (1, 2, 2, 1),
                                 [(0, 0), (1, 1), (1, 1), (0, 0)])
    a = conv(conv(conv(pool, w[2], 1, 0, True), w[3], 2, 1, True),
             w[4], 1, 0, False)
    proj = conv(pool, w[5], 2, 0, False) if shortcut else 0.0
    y = jnp.maximum(a + proj, 0.0)
    b = conv(conv(conv(y, w[7], 1, 0, True), w[8], 1, 1, True),
             w[9], 1, 0, False)
    y = jnp.maximum(b + y, 0.0)
    g = y.sum(axis=(1, 2)) / (y.shape[1] * y.shape[2])
    return jnp.dot(g, w[12]["w"], precision=hi) + w[12]["b"]


def test_tiny_residual_graph_matches_a_forward_by_hand():
    """Both sides run the same float32 operations at HIGHEST on the CPU;
    XLA may fuse and order their sums differently (the mean over H x W
    most of all), so they may part by a few float32 roundings of the
    logits: 1e-6 relative is about eight ulps. Dropping the projection
    from the hand forward must part them by far more."""
    cfg = _tiny()
    shapes = [c.out_shape for c in layer_costs(cfg)]
    assert [shapes[i] for i in (1, 5, 10, 11)] == [
        (8, 8, 8), (4, 4, 32), (4, 4, 32), (1, 1, 32)]
    w = make_weights(cfg, 2 ** 33 + 11)
    x = make_images(cfg, 2 ** 33 + 11, 4)
    got = cnn_float.logits(cfg, w, x, block=4)
    err = correct.logit_rel_err(got, np.asarray(_by_hand(w, x)))
    assert err <= 1e-6, err
    broken = correct.logit_rel_err(got, np.asarray(_by_hand(w, x, False)))
    assert broken > 1e-2, broken


def test_avg_pool_divides_by_its_window():
    cfg = {"input": {"hw": 6, "ch": 2}, "precision": {"dtype": "float32"},
           "layers": [{"kind": "pool", "op": "avg", "k": 2, "stride": 2},
                      {"kind": "fc", "out": 3, "relu": False}]}
    w = make_weights(cfg, 2 ** 33 + 13)
    x = make_images(cfg, 2 ** 33 + 13, 2)
    pooled = x.reshape(2, 3, 2, 3, 2, 2).mean(axis=(2, 4)).reshape(2, -1)
    want = pooled.astype(np.float64) @ np.asarray(w[1]["w"], np.float64) \
        + np.asarray(w[1]["b"], np.float64)
    got = cnn_float.logits(cfg, w, x, block=2)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


# -- the program's layers in the configuration's terms --

def _layer(kind, **kw):
    base = dict(kind=kind, out_ch=0, kernel=0, stride=1, pad=0, groups=1,
                pool="max", relu=True, inputs=())
    return SimpleNamespace(**dict(base, **kw))


def _stub_program():
    """``_tiny`` as a program's model would spell it."""
    conv = lambda m, k, s=1, relu=True, inputs=(): _layer(
        "conv", out_ch=m, kernel=k, stride=s, pad=k // 2, relu=relu,
        inputs=inputs)
    return SimpleNamespace(layers=(
        conv(8, 3, inputs=(-1,)),
        _layer("pool", kernel=3, stride=2, pad=1),
        conv(8, 1), conv(8, 3, 2), conv(32, 1, relu=False),
        conv(32, 1, 2, relu=False, inputs=(1,)),
        _layer("add", inputs=(4, 5)),
        conv(8, 1), conv(8, 3), conv(32, 1, relu=False),
        _layer("add", inputs=(9, 6)),
        _layer("gap"),
        _layer("fc", out_ch=10, relu=False)))


def test_program_graph_maps_to_the_format():
    assert _program_layers(_stub_program()) == _tiny()["layers"]


def test_program_kind_unknown_to_the_map_raises():
    model = _stub_program()
    model.layers = model.layers[:11] + (_layer("softmax"),)
    with pytest.raises(ValueError, match="layer 11 has kind 'softmax'"):
        _program_layers(model)


@pytest.mark.parametrize("name", ["alexnet", "vgg16"])
def test_program_chain_maps_to_its_config_file(name):
    """A chain has no ``inputs`` and no pool pad: no ``in``, no ``pad``."""
    from repro.configs import get_config
    assert _program_layers(get_config(name)) == _config(name)["layers"]
