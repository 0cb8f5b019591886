"""Operations and bytes of each layer, from the configuration's published
shapes.

The counts never look at how the program implements a layer: conv1 of
AlexNet is 11x11x3 taps over a 227x227 image here, whatever layout the
kernel runs it in. So a kernel's roofline share reads the same work
whatever implements it.

Operations are 2 x multiply-accumulates, with ``groups`` honoured.
Bytes are the layer's input + weights + bias + output, each read or
written once, at the configuration's element size.

The layers form a graph. A layer may name the earlier layers it reads
in ``"in"`` (indices; -1 is the image); without it, it reads the layer
before it (the image, for layer 0). Only an ``add`` reads more than one
layer: the elementwise sum of equal shapes, then ReLU where ``relu``.
``gap`` is the mean over H x W. Neither has weights, and both count 0
model operations, as published counts of residual nets do; their bytes
are their inputs and their output. A pool may carry ``pad`` (a max
pool pads with -inf); an average pool takes none. The network's output
is the last layer's.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List, Tuple

ELEMENT_BYTES = {"float32": 4, "bfloat16": 2, "int8": 1}


@dataclass(frozen=True)
class LayerCost:
    index: int
    kind: str                  # conv | pool | lrn | fc | add | gap
    in_shape: Tuple[int, int, int]   # an add's: one input's
    out_shape: Tuple[int, int, int]
    flops: int                 # per image
    weight_elems: int          # weights + bias
    act_elems: int             # every input + output, per image

    def bytes(self, batch: int, elem: int) -> int:
        return (self.weight_elems + batch * self.act_elems) * elem


def _inputs(i: int, l: dict) -> List[int]:
    """The indices layer ``i`` reads (-1: the image), checked."""
    ins = list(l.get("in", [i - 1]))
    if any(not isinstance(j, int) or not -1 <= j < i for j in ins):
        raise ValueError(f"layer {i} ({l['kind']}): inputs {ins} must be "
                         f"earlier layers (-1 is the image)")
    if l["kind"] == "add":
        if len(ins) < 2:
            raise ValueError(f"layer {i} (add): needs two inputs or more, "
                             f"has {ins}")
    elif len(ins) != 1:
        raise ValueError(f"layer {i} ({l['kind']}): only an add reads more "
                         f"than one input, has {ins}")
    return ins


def layer_costs(cfg: dict) -> List[LayerCost]:
    """One :class:`LayerCost` per entry of ``cfg["layers"]``."""
    shapes = {-1: (cfg["input"]["hw"], cfg["input"]["hw"],
                   cfg["input"]["ch"])}
    out: List[LayerCost] = []
    for i, l in enumerate(cfg["layers"]):
        kind = l["kind"]
        ins = _inputs(i, l)
        h, w, c = shapes[ins[0]]
        flops = weights = 0
        if kind == "conv":
            k, s, p, g, m = l["k"], l["stride"], l["pad"], l["groups"], \
                l["out"]
            if c % g or m % g:
                raise ValueError(f"layer {i}: groups={g} must divide "
                                 f"{c} in and {m} out channels")
            oh = (h + 2 * p - k) // s + 1
            ow = (w + 2 * p - k) // s + 1
            flops = 2 * oh * ow * m * k * k * (c // g)
            weights = k * k * (c // g) * m + m
            oshape = (oh, ow, m)
        elif kind == "pool":
            k, s, p = l["k"], l["stride"], l.get("pad", 0)
            if p and l["op"] != "max":
                raise ValueError(f"layer {i} (pool): only a max pool takes "
                                 f"a pad, not {l['op']!r}")
            oshape = ((h + 2 * p - k) // s + 1, (w + 2 * p - k) // s + 1, c)
        elif kind == "lrn":
            oshape = (h, w, c)
        elif kind == "fc":
            m = l["out"]
            flops = 2 * h * w * c * m
            weights = h * w * c * m + m
            oshape = (1, 1, m)
        elif kind == "add":
            if any(shapes[j] != (h, w, c) for j in ins):
                raise ValueError(f"layer {i} (add): input shapes differ: "
                                 f"{[shapes[j] for j in ins]}")
            oshape = (h, w, c)
        elif kind == "gap":
            oshape = (1, 1, c)
        else:
            raise ValueError(f"layer {i}: unknown kind {kind!r}")
        act = len(ins) * h * w * c + oshape[0] * oshape[1] * oshape[2]
        out.append(LayerCost(i, kind, (h, w, c), oshape, flops, weights,
                             act))
        shapes[i] = oshape
    return out


def flops_per_image(cfg: dict) -> int:
    """Model operations of one image: conv and FC, 2 x MACs."""
    return sum(l.flops for l in layer_costs(cfg))


def least_time(cfg: dict, kind: str, batch: int, flop_rate: float,
               hbm_bw: float) -> Tuple[float, int]:
    """Least seconds the chip could take for all layers of ``kind`` on a
    batch of ``batch`` images, layer by layer max(ops / peak, bytes /
    bandwidth), summed; and how many such layers one forward runs."""
    elem = ELEMENT_BYTES[cfg["precision"]["dtype"]]
    layers = [l for l in layer_costs(cfg) if l.kind == kind]
    t = sum(max(batch * l.flops / flop_rate,
                l.bytes(batch, elem) / hbm_bw) for l in layers)
    return t, len(layers)
