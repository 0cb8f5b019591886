"""CNN model stack: AlexNet / VGG-16 / ResNet-50 through the PipeCNN
fused pipeline.

:func:`run_group` executes one fusion group of ``fuse_plan(cfg)``:
consecutive conv(+relu)+pool pairs run as ONE fused kernel (the paper's
Conv->Pool channel), LRN runs as its own kernel off the pipeline (the paper
implements LRN separately because of its multi-map access pattern), and FC
layers run through the multi-mode engine in batched-FC mode. In a layer
graph (ResNet-50) a conv with no ReLU runs with the shortcut add that
follows it as ONE kernel too, the add's other input summed in its
epilogue; gap and the padded stem pool run in XLA.
:func:`cnn_forward_stage` folds it over a slice of groups; the whole
forward is ``repro.pipeline.compile_cnn(cfg, spec, params).forward``,
which hands every group its compile-time tiling plan.

One runner serves every precision; the params' type selects it. A plain
param list runs fp32/bf16. A ``repro.quant.QuantizedCNNParams`` (from
``calibrate_cnn``) runs the paper's fixed-point trade: int8 activations
flow between stages, conv/FC kernels accumulate in int32 and requantize in
their epilogues, standalone max-pools run directly on the int8 codes, and
LRN (the one genuinely nonlinear-in-scale stage) dequantizes around its
kernel exactly as PipeCNN runs LRN off the fixed-point pipeline.
"""
from __future__ import annotations

from typing import Any, Dict, List, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.config import (CNNConfig, group_inputs, layer_inputs,
                               layer_shapes)
from repro.kernels import ops
from repro.kernels.ref import pool_ref
from repro.models.layers import dense_init
from repro.quant.calibrate import QuantizedCNNParams
from repro.quant.core import dequantize, quantize


def init_cnn_params(key, cfg: CNNConfig) -> List[Dict[str, Any]]:
    """Per-layer param list aligned with cfg.layers (None for the layers
    with no weights: pool, lrn, add, gap); a layer's input follows its
    ``inputs``."""
    params: List[Any] = []
    shapes = layer_shapes(cfg)
    dtype = jnp.float32 if cfg.dtype == "float32" else jnp.bfloat16
    for i, l in enumerate(cfg.layers):
        ih, iw, c = shapes[layer_inputs(cfg.layers, i)[0]]
        if l.kind == "conv":
            key, k1 = jax.random.split(key)
            cg = c // l.groups
            fan_in = l.kernel * l.kernel * cg
            w = (jax.random.normal(k1, (l.kernel, l.kernel, cg, l.out_ch),
                                   jnp.float32)
                 * np.sqrt(2.0 / fan_in)).astype(dtype)
            params.append({"w": w, "b": jnp.zeros((l.out_ch,), dtype)})
        elif l.kind == "fc":
            key, k1 = jax.random.split(key)
            params.append({
                "w": dense_init(k1, (ih * iw * c, l.out_ch), dtype),
                "b": jnp.zeros((l.out_ch,), dtype)})
        else:
            params.append(None)
    return params


def fuse_plan(cfg: CNNConfig) -> List[Tuple[int, ...]]:
    """Group layer indices into PipeCNN pipeline stages.

    conv immediately followed by pool  -> fused (conv+pool) kernel launch;
    lrn stays standalone (off-pipeline, as in the paper); fc standalone.
    One shared implementation with the config validator
    (``core.config.fuse_groups``), so execution and validation can never
    disagree on the grouping.
    """
    from repro.core.config import fuse_groups
    return fuse_groups(cfg.layers)


def run_group(params, x: jax.Array, cfg: CNNConfig,
              group: Tuple[int, ...], *,
              residual: Optional[jax.Array] = None,
              plans: Optional[Mapping[Tuple[int, ...], Any]] = None,
              use_pallas: bool = False) -> jax.Array:
    """Execute ONE fusion group — the one place a layer kind is run.

    This is the stage-sliceable unit the distributed serving engine
    partitions over pipeline stages (``repro.serve.stage_planner``).
    ``params`` is a fp32/bf16 param list, or a ``QuantizedCNNParams``
    whose group runs on int8 codes: every scale it needs is static inside
    the params, so a stage can start from any group boundary given that
    boundary's codes.

    ``x`` is the output of the layer the group's first layer reads;
    ``residual`` the add's other input (``core.config.group_inputs``),
    for a conv fused with an add (summed in the kernel's epilogue) or a
    lone add.

    With ``use_pallas``, a conv or fc group runs the tiling
    ``plans[group]`` (a ``ConvPlan``/``GemmPlan`` that ``compile_cnn``
    froze); the reference path needs no plans.
    """
    quant = isinstance(params, QuantizedCNNParams)
    l = cfg.layers[group[0]]
    p = params.layers[group[0]] if quant else params[group[0]]
    if l.kind == "pool":
        # max-pool commutes with the int8 map: pool the codes, keep scale
        return pool_ref(x, l.pool, l.kernel, l.stride, pad=l.pad)
    if l.kind == "gap":
        return jnp.mean(x, axis=(1, 2), keepdims=True)
    if l.kind == "add":
        y = x + residual
        return jnp.maximum(y, 0.0) if l.relu else y
    if l.kind == "lrn":
        if not quant:
            return ops.lrn(x, use_pallas=use_pallas)
        # LRN is nonlinear in scale — run it off the fixed-point
        # pipeline (as PipeCNN does) and requantize its output
        y = ops.lrn(dequantize(x, p.x_scale), use_pallas=use_pallas)
        return quantize(y, p.y_scale)
    plan = plans[group] if use_pallas else None
    if quant:
        w, b, epilogue = p.w_q, p.b, dict(scale=p.scale,
                                           out_scale=p.y_scale)
    else:
        w, b, epilogue = p["w"], p["b"], {}
    if l.kind == "conv":
        tail = cfg.layers[group[1]] if len(group) == 2 else None
        pool = tail if tail is not None and tail.kind == "pool" else None
        relu = l.relu
        if tail is not None and tail.kind == "add":
            epilogue["residual"], relu = residual, tail.relu
        # grouped conv (AlexNet two-tower) runs INSIDE the one kernel:
        # the M-tile grid axis spans groups, no concat on the hot path
        return ops.fused_conv(x, w, b, stride=l.stride, pad=l.pad,
                              relu=relu,
                              pool=(pool.pool if pool else None),
                              pool_k=(pool.kernel if pool else 2),
                              pool_s=(pool.stride if pool else 2),
                              use_pallas=use_pallas, groups=l.groups,
                              plan=plan, vmem_budget=cfg.vmem_budget,
                              **epilogue)
    if l.kind == "fc":
        blocks = ({} if plan is None
                  else dict(bm=plan.bm, bn=plan.bn, bk=plan.bk))
        return ops.fc(x.reshape(x.shape[0], -1), w, b, relu=l.relu,
                      use_pallas=use_pallas, **blocks, **epilogue)
    raise ValueError(f"unknown layer kind {l.kind!r}")


def cnn_forward_stage(params, x: jax.Array, cfg: CNNConfig, groups, *,
                      plans: Optional[Mapping[Tuple[int, ...], Any]] = None,
                      use_pallas: bool = False) -> jax.Array:
    """Run a contiguous slice of fusion groups — one pipeline STAGE.

    ``x`` is the output of the layer before the slice's first (the image
    batch for the whole network). The fold keeps each activation a later
    group of the slice reads, by layer index, and drops it after the
    last such group: in a chain that is the one ``x`` passed along; in
    ResNet a block's input waits for its shortcut add.

    With ``QuantizedCNNParams``, ``x`` is the boundary activation: int8
    codes (any interior boundary) or the raw fp32 image batch for the
    first stage, which this function quantizes at the network edge.
    """
    if isinstance(params, QuantizedCNNParams) and x.dtype != jnp.int8:
        x = quantize(x, params.in_scale)
    reads = [group_inputs(cfg.layers, g) for g in groups]
    last = {j: k for k, ins in enumerate(reads) for j in ins}
    acts = {groups[0][0] - 1: x}
    for k, (group, ins) in enumerate(zip(groups, reads)):
        x = run_group(params, acts[ins[0]], cfg, group,
                      residual=acts[ins[1]] if len(ins) > 1 else None,
                      plans=plans, use_pallas=use_pallas)
        for j in set(ins):
            if last[j] == k:
                del acts[j]
        acts[group[-1]] = x
    return x


def classification_flops(cfg: CNNConfig) -> int:
    from repro.core.config import flops_per_image
    return flops_per_image(cfg)
