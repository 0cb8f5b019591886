"""Post-training calibration: fp32 CNN params -> int8 pipeline params.

PipeCNN fixes its fixed-point positions offline and serves in fixed-point;
this module is that step for the TPU repro. ``calibrate_cnn`` runs the
fp32 reference forward over a calibration stream, observes the activation
range at every pipeline-stage boundary (the same conv(+pool) fusion
groups ``models.cnn.fuse_plan`` executes), and emits a
:class:`QuantizedCNNParams`:

  * weights — per-output-channel symmetric int8 (one scale per feature,
    the standard PTQ setting that keeps conv error small);
  * activations — per-tensor scales from the observed ranges; each conv /
    fc / lrn stage's ``y_scale`` is the requantize target its kernel
    epilogue quantizes into (the NEXT stage's input scale);
  * standalone max-pool stages pass the scale through unchanged — max
    commutes with the monotone int8 mapping, so pooling runs directly on
    the int8 codes;
  * the final classifier keeps fp32 output (``y_scale=None``): logits
    stay full-precision for argmax/softmax.

Scales are python floats, so they ride through ``jax.jit`` as static
requantize constants baked into the kernels. The whole container is a
registered pytree (int8 weights/biases are leaves, scales are aux data),
so ``jax.jit(lambda p, x: ...)(qparams, x)`` works unchanged in the
serving path.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Iterable, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.quant.core import quantize_channelwise
from repro.quant.observers import make_observer


@dataclass
class QuantLayer:
    """Quantized state for one layer index of a CNNConfig.

    ``scale`` is the precomputed combined requantize multiplier
    ``x_scale * w_scale`` (shape (M,)) the kernel epilogue applies to the
    int32 accumulator; ``y_scale`` is the output quantization step (None
    => fp32 output, the final classifier).
    """
    kind: str                                  # "conv" | "fc" | "lrn" | "pool"
    x_scale: float = 1.0
    y_scale: Optional[float] = None
    w_q: Optional[jax.Array] = None            # int8
    w_scale: Optional[jax.Array] = None        # fp32 (M,), per out-channel
    scale: Optional[jax.Array] = None          # fp32 (M,) = x_scale * w_scale
    b: Optional[jax.Array] = None              # fp32 bias


@dataclass
class QuantizedCNNParams:
    """Per-layer quantized params aligned with ``cfg.layers`` (None for
    layer indices consumed by a fused group or needing no state)."""
    layers: List[Optional[QuantLayer]]
    in_scale: float = 1.0                      # network-input quantization


def _ql_flatten(ql: QuantLayer):
    return (ql.w_q, ql.w_scale, ql.scale, ql.b), \
        (ql.kind, ql.x_scale, ql.y_scale)


def _ql_unflatten(aux, children) -> QuantLayer:
    kind, x_scale, y_scale = aux
    w_q, w_scale, scale, b = children
    return QuantLayer(kind=kind, x_scale=x_scale, y_scale=y_scale,
                      w_q=w_q, w_scale=w_scale, scale=scale, b=b)


def _qp_flatten(qp: QuantizedCNNParams):
    return (qp.layers,), (qp.in_scale,)


def _qp_unflatten(aux, children) -> QuantizedCNNParams:
    return QuantizedCNNParams(layers=list(children[0]), in_scale=aux[0])


jax.tree_util.register_pytree_node(QuantLayer, _ql_flatten, _ql_unflatten)
jax.tree_util.register_pytree_node(QuantizedCNNParams, _qp_flatten,
                                   _qp_unflatten)


def group_forward_ref(params, x: jax.Array, cfg
                      ) -> Iterable[Tuple[Tuple[int, ...], jax.Array]]:
    """Reference forward (no Pallas), one fusion group at a time.

    Yields ``(group, activation_after_group)`` for every group of
    ``fuse_plan(cfg)`` — the boundaries the activation observers watch
    (and the per-layer comparison points of the accuracy harness). With
    ``QuantizedCNNParams`` the activations are the int8 pipeline's
    (codes, fp32 logits after the classifier).
    """
    from repro.models.cnn import cnn_forward_stage, fuse_plan

    for group in fuse_plan(cfg):
        x = cnn_forward_stage(params, x, cfg, (group,))
        yield group, x


def calibrate_cnn(params, calib, cfg, *,
                  observer: str = "absmax") -> QuantizedCNNParams:
    """Calibrate + quantize a CNN for int8 serving.

    ``calib`` is one (B, H, W, C) batch or an iterable of batches (the
    calibration set). Deterministic: the same params and batches always
    produce identical scales and int8 codes — the serving path and the
    accuracy harness both rely on this.
    """
    from repro.models.cnn import fuse_plan

    batches = [calib] if hasattr(calib, "shape") else list(calib)
    if not batches:
        raise ValueError("calibration set is empty")
    plan = fuse_plan(cfg)

    # observe only boundaries whose scale is consumed: standalone
    # max-pool groups pass the incoming scale through, and the final
    # group keeps fp32 output — skipping them avoids a device reduction
    # + host sync per group per calibration batch
    def needs_scale(gi: int) -> bool:
        return (gi != len(plan) - 1
                and cfg.layers[plan[gi][0]].kind != "pool")

    obs_in = make_observer(observer)
    obs = [make_observer(observer) if needs_scale(gi) else None
           for gi in range(len(plan))]
    for xb in batches:
        obs_in.update(xb)
        for gi, (_, act) in enumerate(group_forward_ref(params, xb, cfg)):
            if obs[gi] is not None:
                obs[gi].update(act)

    layers: List[Optional[QuantLayer]] = [None] * len(cfg.layers)
    s = obs_in.scale()
    in_scale = s
    for gi, group in enumerate(plan):
        i = group[0]
        l = cfg.layers[i]
        if l.kind in ("conv", "fc"):
            p = params[i]
            w_q, w_scale = quantize_channelwise(p["w"], axis=-1)
            # the final group keeps fp32 output: logits are never requantized
            y = None if gi == len(plan) - 1 else obs[gi].scale()
            layers[i] = QuantLayer(
                kind=l.kind, x_scale=s, y_scale=y, w_q=w_q,
                w_scale=w_scale, scale=w_scale * jnp.float32(s),
                b=p["b"].astype(jnp.float32))
            s = y if y is not None else s
        elif l.kind == "lrn":
            y = obs[gi].scale()
            layers[i] = QuantLayer(kind="lrn", x_scale=s, y_scale=y)
            s = y
        elif l.kind == "pool":
            if l.pool != "max":
                raise NotImplementedError(
                    "standalone avg-pool has no int8 passthrough; "
                    "dequantize first")
            # max-pool is scale-invariant on int8 codes: passthrough
            layers[i] = QuantLayer(kind="pool", x_scale=s, y_scale=s)
    return QuantizedCNNParams(layers=layers, in_scale=in_scale)
