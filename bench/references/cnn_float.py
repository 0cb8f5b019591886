"""Plain float32 reference of a CNN layer graph, built from the
configuration alone (it imports nothing of the program).

Layers: conv (``lax.conv_general_dilated``, groups as feature groups)
+ bias + ReLU; max pool (padded with -inf where ``pad``) and average
pool (VALID windows, divided by k * k); LRN across channels; FC (NHWC
flatten) + bias (+ ReLU); ``add``, the sum of the layers it names, then
ReLU where ``relu``; ``gap``, the mean over H x W. A layer reads the
layers its ``in`` names (-1 is the image), else the one before it; the
logits are the last layer's output. Every dot runs at ``highest``.

LRN is the one the configuration states. For ``"form": "pwl"`` that is
the paper's piecewise-linear z**-beta (PipeCNN, section III): z =
k + (alpha / n) * sum of squares over n neighbouring channels, its
segment read off the float's exponent and top ``sub_bits`` mantissa
bits, and each segment a chord of z**-beta lowered by half its largest
deviation. ``"form": "exact"`` computes z**-beta itself.

The controls, the precision one step below the configuration's float32
at ``highest``, bfloat16 three passes:

  ``passes="high"``         every dot spelled out as a_hi*b_hi + a_hi*b_lo
                            + a_lo*b_hi, the parts rounded to bfloat16 by
                            ``lax.reduce_precision`` (which the compiler
                            keeps; a cast pair to bfloat16 and back it
                            may fold into a one-pass bfloat16 dot), each
                            product exact and summed in float32. Reads
                            the same on any backend.
  ``passes="high-native"``  every dot at ``Precision.HIGH``: the chip's
                            own three-pass mode (a CPU ignores it).
"""
from __future__ import annotations

import functools
import json
from typing import Any, List

import numpy as np


def pwl_table(beta: float, sub_bits: int, z_exp, fit_points: int):
    """Slopes and intercepts (float32) of the PWL z**-beta, one pair per
    segment z in 2**e * [1 + j / 2**sub_bits, 1 + (j + 1) / 2**sub_bits)."""
    e0, e1 = z_exp
    n_sub = 2 ** sub_bits
    edges = np.array([2.0 ** e * (1.0 + j / n_sub)
                      for e in range(e0, e1) for j in range(n_sub)]
                     + [2.0 ** e1])
    f = edges ** -beta
    slope = np.diff(f) / np.diff(edges)
    icpt = f[:-1] - slope * edges[:-1]
    for i in range(len(slope)):
        z = np.linspace(edges[i], edges[i + 1], fit_points)
        icpt[i] -= ((slope[i] * z + icpt[i]) - z ** -beta).max() / 2.0
    return slope.astype(np.float32), icpt.astype(np.float32)


CONTROLS = ("high", "high-native")


def _split(a):
    """a = hi + lo + O(2**-16 a), hi and lo bfloat16 values in float32."""
    import jax
    hi = jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)
    lo = jax.lax.reduce_precision(a - hi, exponent_bits=8, mantissa_bits=7)
    return hi, lo


def _dot(op, a, b, passes: str):
    """``op(a, b, precision=...)`` at ``passes``."""
    import jax
    if passes == "highest":
        return op(a, b, precision=jax.lax.Precision.HIGHEST)
    if passes == "high-native":
        return op(a, b, precision=jax.lax.Precision.HIGH)
    if passes != "high":
        raise ValueError(f"passes={passes!r}: 'highest', 'high' or "
                         f"'high-native'")
    ah, al = _split(a)
    bh, bl = _split(b)
    hi = jax.lax.Precision.HIGHEST
    return op(al, bh, precision=hi) + op(ah, bl, precision=hi) \
        + op(ah, bh, precision=hi)


def _lrn(x, p: dict):
    import jax
    import jax.numpy as jnp
    n, half = p["n"], p["n"] // 2
    sq = jnp.square(x)
    c = x.shape[-1]
    padded = jnp.pad(sq, ((0, 0),) * 3 + ((half, half),))
    acc = sum(padded[..., d:d + c] for d in range(n))
    alpha = p["alpha"] / n if p["alpha_over_n"] else p["alpha"]
    z = p["k"] + alpha * acc
    if p["form"] == "exact":
        return x * z ** -p["beta"]
    slope, icpt = pwl_table(p["beta"], p["sub_bits"], p["z_exp"],
                            p["fit_points"])
    shift = 23 - p["sub_bits"]
    base = (127 + p["z_exp"][0]) << p["sub_bits"]
    seg = jnp.clip((jax.lax.bitcast_convert_type(z, jnp.int32) >> shift)
                   - base, 0, len(slope) - 1)
    return x * (jnp.asarray(slope)[seg] * z + jnp.asarray(icpt)[seg])


def _forward(cfg: dict, passes: str, params: List[Any], x):
    import jax
    import jax.numpy as jnp
    layers = cfg["layers"]
    # outputs some later layer reads by index (-1: the image)
    named = {j for l in layers for j in l.get("in", ())}
    kept = {-1: x} if -1 in named else {}
    for i, (l, p) in enumerate(zip(layers, params)):
        kind = l["kind"]
        ins = [kept[j] for j in l.get("in", ())]
        if ins:
            x = ins[0]
        if kind == "conv":
            conv = functools.partial(
                jax.lax.conv_general_dilated,
                window_strides=(l["stride"],) * 2,
                padding=[(l["pad"], l["pad"])] * 2,
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
                feature_group_count=l["groups"])
            x = _dot(conv, x, p["w"], passes) + p["b"]
            if l["relu"]:
                x = jnp.maximum(x, 0.0)
        elif kind == "pool":
            k, s, pad = l["k"], l["stride"], l.get("pad", 0)
            if l["op"] == "max":
                x = jax.lax.reduce_window(
                    x, -jnp.inf, jax.lax.max, (1, k, k, 1), (1, s, s, 1),
                    [(0, 0), (pad, pad), (pad, pad), (0, 0)])
            elif l["op"] == "avg" and not pad:
                x = jax.lax.reduce_window(x, 0.0, jax.lax.add, (1, k, k, 1),
                                          (1, s, s, 1), "VALID") / (k * k)
            else:
                raise ValueError(f"layer {i}: pool op {l['op']!r} with pad "
                                 f"{pad} not in the reference")
        elif kind == "lrn":
            x = _lrn(x, cfg["lrn"])
        elif kind == "fc":
            x = x.reshape(x.shape[0], -1)
            x = _dot(jnp.dot, x, p["w"], passes) + p["b"]
            if l["relu"]:
                x = jnp.maximum(x, 0.0)
        elif kind == "add":
            for y in ins[1:]:
                x = x + y
            if l["relu"]:
                x = jnp.maximum(x, 0.0)
        elif kind == "gap":
            x = jnp.mean(x, axis=(1, 2), keepdims=True)
        else:
            raise ValueError(f"layer {i}: unknown layer kind {kind!r}")
        if i in named:
            kept[i] = x
    return x


@functools.lru_cache(maxsize=None)
def _jitted(cfg_json: str, passes: str):
    import jax
    return jax.jit(functools.partial(_forward, json.loads(cfg_json), passes))


def logits(cfg: dict, params: List[Any], images: np.ndarray, *,
           passes: str = "highest", block: int = 8) -> np.ndarray:
    """Reference logits of ``images`` (n, H, W, C), ``block`` images per
    call, on the default device."""
    import jax
    fwd = _jitted(json.dumps(cfg, sort_keys=True), passes)
    dev = jax.devices()[0]
    params = jax.device_put(params, dev)
    out = []
    for i in range(0, len(images), block):
        chunk = images[i:i + block]
        pad = block - len(chunk)
        if pad:
            chunk = np.concatenate([chunk, np.zeros((pad,) + chunk.shape[1:],
                                                    chunk.dtype)])
        out.append(np.asarray(fwd(params, jax.device_put(chunk, dev)))
                   [:block - pad])
    return np.concatenate(out)
