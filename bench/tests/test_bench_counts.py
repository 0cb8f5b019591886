"""Operations and bytes come from the configurations' published shapes."""
import json
import os

import pytest

from bench.counts import flops_per_image, layer_costs, least_time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(__file__)))


def _config(name):
    with open(os.path.join(ROOT, "bench", "configs", name + ".json")) as f:
        return json.load(f)


def _vgg16():
    """VGG-16, configuration D of arXiv:1409.1556 (Table 1)."""
    conv = lambda m: {"kind": "conv", "out": m, "k": 3, "stride": 1,
                      "pad": 1, "groups": 1, "relu": True}
    pool = {"kind": "pool", "op": "max", "k": 2, "stride": 2}
    layers = []
    for n, m in ((2, 64), (2, 128), (3, 256), (3, 512), (3, 512)):
        layers += [conv(m)] * n + [pool]
    layers += [{"kind": "fc", "out": 4096, "relu": True},
               {"kind": "fc", "out": 4096, "relu": True},
               {"kind": "fc", "out": 1000, "relu": False}]
    return {"input": {"hw": 224, "ch": 3}, "layers": layers,
            "precision": {"dtype": "float32"}}


def _resnet50():
    """ResNet-50 v1.5: arXiv:1512.03385 Table 1, the 50-layer column,
    with each bottleneck's stride on its 3x3 conv (torchvision's
    ``resnet50``). Batch-norm is folded into each conv's weight and bias.
    A stage's first block reads its input through a projection, a 1x1
    conv at the block's stride with no ReLU; every block ends in an add
    with ReLU."""
    conv = lambda m, k, s, relu=True, **kw: dict(
        {"kind": "conv", "out": m, "k": k, "stride": s, "pad": k // 2,
         "groups": 1, "relu": relu}, **kw)
    layers = [conv(64, 7, 2),
              {"kind": "pool", "op": "max", "k": 3, "stride": 2, "pad": 1}]
    for n, width, stride in ((3, 64, 1), (4, 128, 2), (6, 256, 2),
                             (3, 512, 2)):
        for b in range(n):
            x = len(layers) - 1            # the block's input
            s = stride if b == 0 else 1
            layers += [conv(width, 1, 1), conv(width, 3, s),
                       conv(4 * width, 1, 1, relu=False)]
            short = x
            if b == 0:
                layers.append(conv(4 * width, 1, s, relu=False, **{"in": [x]}))
                short = len(layers) - 1
            main = len(layers) - 2 if b == 0 else len(layers) - 1
            layers.append({"kind": "add", "in": [main, short], "relu": True})
    layers += [{"kind": "gap"}, {"kind": "fc", "out": 1000, "relu": False}]
    return {"input": {"hw": 224, "ch": 3}, "layers": layers,
            "precision": {"dtype": "float32"}}


def test_resnet50_v1_5_counts_pinned():
    cfg = _resnet50()
    costs = layer_costs(cfg)
    kinds = [c.kind for c in costs]
    assert len(costs) == 72
    assert kinds.count("conv") == 53 and kinds.count("add") == 16
    # 4.089 G multiply-accumulates: the adds and the pools count none
    assert flops_per_image(cfg) == 8_178_368_512
    # torchvision's 25,557,032 less batch-norm's second parameter per
    # channel (26,560 channels), folded into the conv's weight and bias
    assert sum(c.weight_elems for c in costs) == 25_530_472
    assert costs[1].out_shape == (56, 56, 64)        # the padded pool
    assert costs[-2].out_shape == (1, 1, 2048)       # gap
    assert costs[-1].out_shape == (1, 1, 1000)


def test_add_and_gap_count_their_bytes_and_no_operations():
    costs = layer_costs(_resnet50())
    add = next(c for c in costs if c.kind == "add")
    assert add.flops == add.weight_elems == 0
    assert add.in_shape == add.out_shape == (56, 56, 256)
    assert add.act_elems == 3 * 56 * 56 * 256        # two inputs, one output
    gap = costs[-2]
    assert gap.flops == gap.weight_elems == 0
    assert gap.act_elems == 7 * 7 * 2048 + 2048


def _graph(*layers):
    return {"input": {"hw": 8, "ch": 4}, "layers": list(layers),
            "precision": {"dtype": "float32"}}


_CONV = {"kind": "conv", "out": 4, "k": 3, "stride": 1, "pad": 1,
         "groups": 1, "relu": True}


@pytest.mark.parametrize("cfg,layer", [
    (_graph(_CONV, dict(_CONV, **{"in": [2]}), _CONV), 1),       # forward
    (_graph(_CONV, dict(_CONV, **{"in": [1]})), 1),              # itself
    (_graph(_CONV, dict(_CONV, **{"in": [-2]})), 1),             # before -1
    (_graph(_CONV, dict(_CONV, stride=2),
            {"kind": "add", "in": [0, 1], "relu": True}), 2),    # shapes
    (_graph(_CONV, dict(_CONV, out=8),
            {"kind": "add", "in": [0, 1], "relu": True}), 2),    # channels
    (_graph(_CONV, {"kind": "add", "in": [0], "relu": True}), 1),  # one in
    (_graph(_CONV, _CONV, dict(_CONV, **{"in": [0, 1]})), 2),    # not add
    (_graph(_CONV, {"kind": "pool", "op": "avg", "k": 3, "stride": 2,
                    "pad": 1}), 1),                              # avg pad
    (_graph(_CONV, {"kind": "softmax"}), 1),                     # kind
])
def test_malformed_graph_names_its_layer(cfg, layer):
    with pytest.raises(ValueError, match=rf"^layer {layer}\b"):
        layer_costs(cfg)


def test_alexnet_flops_pinned():
    # 2 x MACs over 5 convs (conv2/4/5 in two groups) and 3 FC layers
    assert flops_per_image(_config("alexnet")) == 1_448_813_632


def test_vgg16_flops_pinned():
    assert flops_per_image(_vgg16()) == 30_940_528_640


@pytest.mark.parametrize("name", ["alexnet", "alexnet-dp4"])
def test_alexnet_conv1_counted_at_published_shape(name):
    conv1 = layer_costs(_config(name))[0]
    # 11x11x3 taps, 96 maps of 55x55: not the kernel's space-to-depth
    # 57x57x48 input with 3x3 taps
    assert conv1.in_shape == (227, 227, 3)
    assert conv1.out_shape == (55, 55, 96)
    assert conv1.flops == 2 * 55 * 55 * 96 * 11 * 11 * 3
    assert conv1.weight_elems == 11 * 11 * 3 * 96 + 96


def test_grouped_conv_halves_the_work():
    cfg = _config("alexnet")
    conv2 = layer_costs(cfg)[3]
    assert conv2.flops == 2 * 27 * 27 * 256 * 5 * 5 * (96 // 2)


def test_least_time_takes_the_slower_bound():
    cfg = _config("alexnet")
    # FC at batch 32: 58.6 M weights (235 MB) dominate 2 x 58.6 M x 32
    # operations at any realistic peak, so the HBM bound wins
    t, n = least_time(cfg, "fc", 32, flop_rate=197e12, hbm_bw=819e9)
    assert n == 3
    fc = [l for l in layer_costs(cfg) if l.kind == "fc"]
    assert t == pytest.approx(sum(l.bytes(32, 4) for l in fc) / 819e9)
    t, n = least_time(cfg, "fc", 32, flop_rate=1e9, hbm_bw=819e9)
    assert t == pytest.approx(32 * sum(l.flops for l in fc) / 1e9)
