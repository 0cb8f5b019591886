"""matmul_pipe — PipeCNN's multi-mode compute engine in FC/GEMM mode.

The paper's single convolution kernel serves conv (CN = K*K*C') and FC
(CN = C') by flattening to one MAC loop. On TPU the unified engine is a
blocked GEMM: conv mode is `conv_pipe`'s im2col matmul; this kernel is the
FC mode, with PipeCNN's batched-FC weight reuse (batch rows share the
weight tile resident in VMEM).

  BK <-> VEC_SIZE  (input vectorization per cycle)
  BN <-> CU_NUM    (parallel output features)

fp32 VMEM scratch accumulates across K-tiles (grid k-axis last, arbitrary
semantics); bias + ReLU fuse into the epilogue.

Fixed-point mode (the classifier side of the int8 pipeline): int8 x/w
tiles, int32 accumulation, and the same fused requantize -> bias -> ReLU
epilogue as the conv kernel — ``scale`` is the per-output-feature
s_x * s_w[n] multiplier, ``out_scale`` (static) requantizes hidden-FC
outputs to int8 for the next layer (None keeps fp32 logits).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.mode import resolve_interpret


def _matmul_kernel(x_ref, w_ref, b_ref, *refs, n_k: int, relu: bool,
                   quantized: bool = False,
                   out_scale: Optional[float] = None):
    if quantized:
        s_ref, refs = refs[0], refs[1:]
    o_ref, acc_ref = refs
    k_idx = pl.program_id(2)

    @pl.when(k_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # fp32 runs at fp32 on the MXU (the default rounds operands to bf16)
    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        precision=None if quantized else jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.int32 if quantized else jnp.float32)

    @pl.when(k_idx == n_k - 1)
    def _epilogue():
        y = acc_ref[...].astype(jnp.float32)
        if quantized:
            y = y * s_ref[...].astype(jnp.float32)
        y = y + b_ref[...].astype(jnp.float32)
        if relu:
            y = jnp.maximum(y, 0.0)
        if quantized and out_scale is not None:
            # same round/clip as quant.core.quantize (bit-exact parity)
            y = jnp.clip(jnp.round(y / out_scale), -127, 127)
        o_ref[...] = y.astype(o_ref.dtype)


def matmul_pipe(x: jax.Array, w: jax.Array, b: jax.Array = None, *,
                scale: Optional[jax.Array] = None,
                out_scale: Optional[float] = None,
                relu: bool = False, bm: int = 128, bn: int = 128,
                bk: int = 128,
                interpret: Optional[bool] = None) -> jax.Array:
    """y = relu(x @ w + b). x (M, K); w (K, N); b (N,).

    ``scale`` ((N,) fp32) selects the int8 path: x/w int8, int32
    accumulation, requantize epilogue; ``out_scale`` (static float) emits
    int8 instead of fp32. Bias and scale ride as (1, BN) lane rows.
    Compiled (``interpret`` False; None = from the backend), bn/bk must
    be multiples of 128 or the whole dim and bm a multiple of the
    dtype's sublane tile or the whole dim.
    """
    interpret = resolve_interpret(interpret)
    M, K = x.shape
    _, N = w.shape
    quantized = scale is not None
    if b is None:
        b = jnp.zeros((N,), jnp.float32 if quantized else x.dtype)
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, K)

    def padto(a, axis, blk):
        rem = a.shape[axis] % blk
        if not rem:
            return a
        padw = [(0, 0)] * a.ndim
        padw[axis] = (0, blk - rem)
        return jnp.pad(a, padw)

    xp, wp = padto(padto(x, 0, bm), 1, bk), padto(padto(w, 0, bk), 1, bn)
    bp = padto(b.reshape(1, N), 1, bn)
    Mp, Kp = xp.shape
    Np = wp.shape[1]
    grid = (Mp // bm, Np // bn, Kp // bk)

    in_specs = [
        pl.BlockSpec((bm, bk), lambda mi, ni, ki: (mi, ki)),
        pl.BlockSpec((bk, bn), lambda mi, ni, ki: (ki, ni)),
        pl.BlockSpec((1, bn), lambda mi, ni, ki: (0, ni)),
    ]
    args = (xp, wp, bp)
    if quantized:
        in_specs.append(pl.BlockSpec((1, bn), lambda mi, ni, ki: (0, ni)))
        args = args + (padto(scale.astype(jnp.float32).reshape(1, N), 1,
                             bn),)
        out_dtype = jnp.int8 if out_scale is not None else jnp.float32
    else:
        out_dtype = x.dtype

    kern = functools.partial(_matmul_kernel, n_k=grid[2], relu=relu,
                             quantized=quantized, out_scale=out_scale)
    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda mi, ni, ki: (mi, ni)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), out_dtype),
        scratch_shapes=[pltpu.VMEM(
            (bm, bn), jnp.int32 if quantized else jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*args)
    return out[:M, :N]
