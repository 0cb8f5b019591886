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
