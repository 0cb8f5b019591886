"""Seeded weights and inputs. The program and the reference both read
these; neither makes its own.

Weights are made on the device in one jitted call, in the layout the
program serves them in: per layer ``{"w": (k, k, in/groups, out), "b":
(out,)}`` for conv, ``{"w": (h*w*c, out), "b": (out,)}`` for FC (rows in
NHWC flatten order), ``None`` for the layers with no weights (pool, LRN,
add, gap), which draw no key: the keys follow the conv and FC layers.
"""
from __future__ import annotations

from typing import Any, List, Optional

import numpy as np

# one stream per purpose, so adding a draw to one never moves another
WEIGHTS, IMAGES, ORDER, SAMPLE = 1, 2, 3, 4
BIAS_STD = 0.05


def rng(seed: int, stream: int) -> np.random.Generator:
    """A numpy generator for ``stream`` of ``seed`` (any whole number)."""
    return np.random.default_rng(np.random.SeedSequence(
        [seed % 2 ** 64, stream]))


def jax_key(seed: int, stream: int):
    """A JAX key from all bits of ``seed``: ``jax.random.key`` alone keeps
    only the low 32 bits of a larger seed."""
    import jax
    return jax.random.key(int(rng(seed, stream).integers(2 ** 31)))


def param_shapes(cfg: dict) -> List[Optional[tuple]]:
    """``(w_shape, fan_in, relu)`` per conv or FC layer, ``None`` for
    the others; a layer's input shape follows its ``in``."""
    from bench.counts import layer_costs
    out: List[Optional[tuple]] = []
    for l, cost in zip(cfg["layers"], layer_costs(cfg)):
        h, w, c = cost.in_shape
        if l["kind"] == "conv":
            cg = c // l["groups"]
            out.append(((l["k"], l["k"], cg, l["out"]),
                        l["k"] * l["k"] * cg, l["relu"]))
        elif l["kind"] == "fc":
            out.append(((h * w * c, l["out"]), h * w * c, l["relu"]))
        else:
            out.append(None)
    return out


def make_weights(cfg: dict, seed: int, sharding=None) -> List[Any]:
    """All weights of ``cfg`` from ``seed``, made on the device in one
    jitted call (placed by ``sharding`` where given). He-normal weights
    (std sqrt(1/fan_in) ahead of a layer with no ReLU), N(0, 0.05)
    biases."""
    import jax
    import jax.numpy as jnp

    shapes = param_shapes(cfg)
    dtype = jnp.dtype(cfg["precision"]["dtype"])

    def make(key):
        params = []
        for s in shapes:
            if s is None:
                params.append(None)
                continue
            shape, fan_in, relu = s
            key, kw, kb = jax.random.split(key, 3)
            std = np.sqrt((2.0 if relu else 1.0) / fan_in)
            params.append({
                "w": (jax.random.normal(kw, shape, jnp.float32)
                      * std).astype(dtype),
                "b": (jax.random.normal(kb, (shape[-1],), jnp.float32)
                      * BIAS_STD).astype(dtype)})
        return params

    return jax.jit(make, out_shardings=sharding)(jax_key(seed, WEIGHTS))


def make_images(cfg: dict, seed: int, n: int) -> np.ndarray:
    """``n`` standard-normal images (n, H, W, C), float32, on the host."""
    hw, ch = cfg["input"]["hw"], cfg["input"]["ch"]
    return rng(seed, IMAGES).standard_normal((n, hw, hw, ch),
                                             dtype=np.float32)
