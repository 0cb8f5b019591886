"""Pure-jnp oracles for every Pallas kernel (the correctness ground truth).

Each function mirrors one kernel's contract exactly; tests sweep shapes and
dtypes asserting allclose between kernel (interpret=True) and oracle.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.lrn_pwl import LRN_ALPHA, LRN_BETA, LRN_K, LRN_N


def conv_pipe_ref(x, w, b, *, stride=1, pad=0, relu=True, pool=None,
                  pool_k=2, pool_s=2, groups=1, residual=None):
    """Oracle for kernels.conv_pipe (conv + bias (+ residual) + ReLU +
    pool, grouped)."""
    out = jax.lax.conv_general_dilated(
        x, w, (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=groups)
    out = out + b
    if residual is not None:
        out = out + residual
    if relu:
        out = jnp.maximum(out, 0.0)
    if pool is not None:
        out = pool_ref(out, pool, pool_k, pool_s)
    return out


def pool_ref(x, pool="max", k=2, s=2, pad=0):
    """Pooling oracle; int dtypes supported for max (the int8 pipeline
    max-pools directly on codes — max commutes with the monotone
    quantization map, so the scale passes through unchanged). ``pad``
    pads a max pool with its identity (ResNet's 3x3/2 stem pool)."""
    integer = jnp.issubdtype(x.dtype, jnp.integer)
    if pool == "max":
        init = jnp.iinfo(x.dtype).min if integer else -jnp.inf
        red = jax.lax.max
    else:
        if integer:
            raise NotImplementedError(
                "avg-pool on integer codes needs a requantize; "
                "dequantize first")
        if pad:
            raise ValueError("only a max pool takes a pad")
        init, red = 0.0, jax.lax.add
    out = jax.lax.reduce_window(x, jnp.asarray(init, x.dtype), red,
                                (1, k, k, 1), (1, s, s, 1),
                                ((0, 0), (pad, pad), (pad, pad), (0, 0)))
    return out / (k * k) if pool == "avg" else out


def lrn_ref(x, *, n=LRN_N, k=LRN_K, alpha=LRN_ALPHA, beta=LRN_BETA):
    """Exact LRN (the function the PWL kernel approximates)."""
    xf = x.astype(jnp.float32)
    sq = jnp.square(xf)
    half = n // 2
    acc = sq
    for d in range(1, half + 1):
        zpad = jnp.zeros_like(sq[:, :, :, :d])
        acc = acc + jnp.concatenate([sq[:, :, :, d:], zpad], axis=3)
        acc = acc + jnp.concatenate([zpad, sq[:, :, :, :-d]], axis=3)
    z = k + (alpha / n) * acc
    return (xf * z ** (-beta)).astype(x.dtype)


def matmul_pipe_ref(x, w, b=None, *, relu=False):
    y = x.astype(jnp.float32) @ w.astype(jnp.float32)
    if b is not None:
        y = y + b.astype(jnp.float32)
    if relu:
        y = jnp.maximum(y, 0.0)
    return y.astype(x.dtype)


def flash_attention_ref(q, k, v):
    """Causal MHA oracle, fp32 softmax. q/k/v (B,H,S,D)."""
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / np.sqrt(D)
    mask = jnp.tril(jnp.ones((Sq, Sk), bool), k=Sk - Sq)
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    return o.astype(q.dtype)


def decode_attention_ref(q, k_cache, v_cache, new_k, new_v, pos):
    """Oracle for kernels.decode_attention (update then attend).

    q (B,HKV,G,D); caches (B,S,HKV,D); new_k/v (B,HKV,D); pos scalar.
    """
    B, S, HKV, D = k_cache.shape
    at = (jnp.arange(S) == pos)[None, :, None, None]
    ck = jnp.where(at, new_k[:, None], k_cache)
    cv = jnp.where(at, new_v[:, None], v_cache)
    s = jnp.einsum("bhgd,bshd->bhgs", q.astype(jnp.float32),
                   ck.astype(jnp.float32)) / np.sqrt(D)
    s = jnp.where((jnp.arange(S) <= pos)[None, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgs,bshd->bhgd", p, cv.astype(jnp.float32))
    return o.astype(q.dtype), ck, cv
