"""The system under test: ``repro.pipeline.compile_cnn(...).forward``.

``CompiledCNN.forward`` is the compile-once forward the program's
serving paths run; it takes a host batch, copies it to the device(s)
and returns logits. The benchmark hands it the seeded weights and the
configuration's precision and placement, and drives nothing else of
the program.
"""
from __future__ import annotations

import os
import sys
from typing import Any, List

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _program_layers(pcfg) -> List[dict]:
    """The program's layer list in the configuration file's terms.

    Each program layer has ``kind``; a conv ``out_ch``, ``kernel``,
    ``stride``, ``pad``, ``groups``, ``relu``; a pool ``pool`` (its op),
    ``kernel``, ``stride``, ``pad``; an FC ``out_ch``, ``relu``; an
    ``add`` (the sum of its inputs, then ReLU where set) ``relu``; an
    ``lrn`` and a ``gap`` (the mean over H x W) nothing more. Any layer
    may have ``inputs``: a tuple of earlier layer indices it reads, -1
    for the image, ``()`` (or no attribute) for the layer before it.
    They map to ``in``, ``out``, ``k``, ``stride``, ``pad``, ``groups``,
    ``relu`` and ``op``; ``in`` only where ``inputs`` is not empty, a
    pool's ``pad`` only where it is not 0, so a chain maps to a chain.
    A kind not named here is an error.
    """
    out = []
    for i, l in enumerate(pcfg.layers):
        if l.kind == "conv":
            d = {"kind": "conv", "out": l.out_ch, "k": l.kernel,
                 "stride": l.stride, "pad": l.pad, "groups": l.groups,
                 "relu": l.relu}
        elif l.kind == "pool":
            d = {"kind": "pool", "op": l.pool, "k": l.kernel,
                 "stride": l.stride}
            if l.pad:
                d["pad"] = l.pad
        elif l.kind in ("lrn", "gap"):
            d = {"kind": l.kind}
        elif l.kind == "fc":
            d = {"kind": "fc", "out": l.out_ch, "relu": l.relu}
        elif l.kind == "add":
            d = {"kind": "add", "relu": l.relu}
        else:
            raise ValueError(f"the program's layer {i} has kind {l.kind!r}, "
                             f"which the benchmark does not know")
        inputs = getattr(l, "inputs", ())
        if inputs:
            d["in"] = list(inputs)
        out.append(d)
    return out


class System:
    """One compiled forward at the cell's batch and placement.

    ``batch`` is the whole system's batch: the per-chip batch times the
    replicas. ``forward(x)`` takes a host (numpy) batch of that size and
    returns the device logits without waiting for them.
    """

    def __init__(self, cfg: dict, batch_per_chip: int, weights: Any):
        if os.path.join(ROOT, "src") not in sys.path:
            sys.path.insert(0, os.path.join(ROOT, "src"))
        from repro.configs import get_config
        from repro.pipeline import (ExecutionSpec, Placement, Precision,
                                    Serving, compile_cnn)

        pcfg = get_config(cfg["program_arch"])
        if (_program_layers(pcfg) != cfg["layers"]
                or pcfg.input_hw != cfg["input"]["hw"]
                or pcfg.input_ch != cfg["input"]["ch"]
                or pcfg.n_classes != cfg["n_classes"]):
            raise ValueError(f"the program's {cfg['program_arch']!r} is not "
                             f"the configuration {cfg['name']!r}")
        prec = cfg["precision"]
        if prec["matmul"] != "highest" or prec["quant"] != "none":
            raise ValueError(f"{cfg['name']}: this system runs float dots "
                             f"at highest only, not {prec}")
        replicas = cfg["placement"]["replicas"]
        spec = ExecutionSpec(
            precision=Precision(dtype=prec["dtype"]),
            placement=Placement(replicas=replicas),
            serving=Serving(batch=batch_per_chip))
        self.compiled = compile_cnn(pcfg, spec, weights,
                                    with_engine=replicas > 1)
        self.batch = batch_per_chip * replicas
        self.replicas = replicas

    def forward(self, x: np.ndarray):
        return self.compiled.forward(x)

    def close(self) -> None:
        """Drop the program's state (weights, executables)."""
        self.compiled = None


def weight_sharding(cfg: dict):
    """Where the weights are made: replicated over the program's data
    mesh for a data-parallel placement (so ``forward`` copies nothing
    but the batch), the default device otherwise."""
    replicas = cfg["placement"]["replicas"]
    if replicas == 1:
        return None
    import jax
    from jax.sharding import NamedSharding, PartitionSpec
    mesh = jax.make_mesh((replicas, 1), ("data", "pipe"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    return NamedSharding(mesh, PartitionSpec())
