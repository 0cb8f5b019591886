"""lrn_pwl — LRN with PipeCNN's piecewise-linear exponent-segmented LUT.

The paper approximates the LRN power function z^(-beta) piecewise-linearly,
with segment boundaries at powers of 2^(-n): the segment index is read
directly off the float's exponent bits (plus the top n mantissa bits),
avoiding any table-search logic:

    Addr = (bitcast(z) >> Shift_Bit) - base        [paper: Exp >> Shift_Bit + 1]

This transfers to TPU unchanged — exponent extraction is a vector bitcast +
shift, and the LUT is a set of kernel constants. n=2 (4 sub-segments per octave) reproduces
the paper's <= 0.5 % max-error claim on the AlexNet LRN (validated in
tests/benchmarks).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.conv_pipe import SUBLANE, _round_up
from repro.kernels.mode import resolve_interpret

# AlexNet LRN constants
LRN_N = 5
LRN_K = 2.0
LRN_ALPHA = 1e-4
LRN_BETA = 0.75


def build_pwl_lut(beta: float = LRN_BETA, n_sub_bits: int = 2,
                  z_min_exp: int = 0, z_max_exp: int = 16
                  ) -> Tuple[np.ndarray, np.ndarray, int, int]:
    """Slope/intercept LUT for f(z)=z^-beta over z in [2^min, 2^max).

    Segment boundaries must match the bit addressing exactly: the exponent
    plus top-n mantissa bits split each octave into 2^n LINEAR quarters,
    z in 2^e * [1 + j/2^n, 1 + (j+1)/2^n) — the paper's power-of-2^-n
    segmentation ("directly operates on the exponent of the input").
    """
    n_sub = 1 << n_sub_bits
    n_seg = (z_max_exp - z_min_exp) * n_sub
    edges = np.concatenate([
        2.0 ** e * (1.0 + np.arange(n_sub) / n_sub)
        for e in range(z_min_exp, z_max_exp)] + [[2.0 ** z_max_exp]])
    f = edges ** (-beta)
    slope = (f[1:] - f[:-1]) / (edges[1:] - edges[:-1])
    intercept = f[:-1] - slope * edges[:-1]
    # minimax refinement: the chord of a convex f overestimates everywhere
    # inside the segment; shifting it down by half the peak deviation
    # balances the error (halves the chord's max error — what lets n=2 meet
    # the paper's 0.5 % bound).
    for i in range(n_seg):
        zs = np.linspace(edges[i], edges[i + 1], 65)
        dev = (slope[i] * zs + intercept[i]) - zs ** (-beta)
        intercept[i] -= dev.max() / 2.0
    # Addr = (bits >> shift) - base
    shift = 23 - n_sub_bits
    base = (127 + z_min_exp) << n_sub_bits
    return (slope.astype(np.float32), intercept.astype(np.float32),
            shift, base)


def _pwlf(z, slope_lut, intercept_lut, shift: int, base: int):
    """Piecewise-linear f(z) via exponent addressing (z > 0, fp32).

    The LUT is baked into the kernel as constants and read by
    compare-and-select over its segments: the same values a gather would
    fetch, in vector ops Mosaic lowers (it has no 1-D gather)."""
    bits = jax.lax.bitcast_convert_type(z, jnp.int32)
    addr = jnp.clip((bits >> shift) - base, 0, len(slope_lut) - 1)
    slope = jnp.full(z.shape, float(slope_lut[0]), jnp.float32)
    icpt = jnp.full(z.shape, float(intercept_lut[0]), jnp.float32)
    for seg in range(1, len(slope_lut)):
        hit = addr == seg
        slope = jnp.where(hit, float(slope_lut[seg]), slope)
        icpt = jnp.where(hit, float(intercept_lut[seg]), icpt)
    return slope * z + icpt


def _lrn_kernel(x_ref, o_ref, *, n: int, k: float, alpha: float,
                slope_lut, intercept_lut, shift: int, base: int):
    x = x_ref[...].astype(jnp.float32)             # (R_BLK, C) pixels
    c = x.shape[1]
    # cross-feature-map window sum as one MXU matmul with the banded
    # 0/1 matrix band[i, j] = |i - j| <= n // 2 (zero-padded at the edges)
    i = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    band = (jnp.abs(i - j) <= n // 2).astype(jnp.float32)
    acc = jnp.dot(jnp.square(x), band, precision=jax.lax.Precision.HIGHEST,
                  preferred_element_type=jnp.float32)
    z = k + (alpha / n) * acc
    pwlf = _pwlf(z, slope_lut, intercept_lut, shift, base)
    o_ref[...] = (x * pwlf).astype(o_ref.dtype)


def lrn_pwl(x: jax.Array, *, n: int = LRN_N, k: float = LRN_K,
            alpha: float = LRN_ALPHA, beta: float = LRN_BETA,
            n_sub_bits: int = 2, r_blk: int = 512,
            interpret: Optional[bool] = None) -> jax.Array:
    """LRN with the PWL-exponent approximation. x (B, H, W, C).

    Pixels are independent, so the kernel sees x as (B*H*W, C) rows —
    channels on the lanes, ``r_blk`` pixels per grid step (rounded to a
    multiple of 8). ``interpret`` None follows the backend.
    """
    interpret = resolve_interpret(interpret)
    B, H, W, C = x.shape
    slope, icpt, shift, base = build_pwl_lut(beta, n_sub_bits)
    rows = B * H * W
    r_blk = min(_round_up(r_blk, SUBLANE), _round_up(rows, SUBLANE))
    rp = _round_up(rows, r_blk)
    xr = jnp.pad(x.reshape(rows, C), ((0, rp - rows), (0, 0)))
    kern = functools.partial(_lrn_kernel, n=n, k=k, alpha=alpha,
                             slope_lut=tuple(slope.tolist()),
                             intercept_lut=tuple(icpt.tolist()),
                             shift=shift, base=base)
    out = pl.pallas_call(
        kern,
        grid=(rp // r_blk,),
        in_specs=[pl.BlockSpec((r_blk, C), lambda ri: (ri, 0))],
        out_specs=pl.BlockSpec((r_blk, C), lambda ri: (ri, 0)),
        out_shape=jax.ShapeDtypeStruct((rp, C), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(xr)
    return out[:rows].reshape(B, H, W, C)
