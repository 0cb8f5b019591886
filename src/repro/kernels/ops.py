"""jit'd public wrappers over the Pallas kernels, with oracle fallback.

``use_pallas`` selects kernel vs pure-jnp path. The kernels run compiled
on a TPU and in Pallas interpret mode elsewhere (:mod:`repro.kernels.mode`
picks it from the backend when a wrapper is called; ``interpret_mode`` is
the scoped override). The resolved mode is a static argument of every
wrapper, so each mode has jit cache entries of its own. GQA adaptation
for flash attention lives here (kv heads repeated to q heads before the
MHA kernel).
"""
from __future__ import annotations

import functools
from typing import Callable, Tuple

import jax
import jax.numpy as jnp

from repro.core.roofline import VMEM_BYTES
from repro.kernels import ref
from repro.kernels.conv_pipe import LANE, conv_pipe
from repro.kernels.flash_attention import flash_attention
from repro.kernels.lrn_pwl import lrn_pwl
from repro.kernels.matmul_pipe import matmul_pipe
from repro.kernels.mode import get_interpret, interpret_mode
from repro.quant import ref as quant_ref

# the public kernel-wrapper contract — tests/test_api_surface.py snapshots
# this list so a refactor cannot silently drop or rename an entry point
__all__ = [
    "attention", "fc", "fused_conv", "get_interpret", "interpret_mode",
    "lrn",
]


def _kernel_wrapper(static_argnames: Tuple[str, ...]) -> Callable:
    """jit ``f`` with ``static_argnames`` plus ``interpret``, filled with
    the mode resolved at call time — so the mode is part of the jit key
    and a trace is never reused across modes."""
    def deco(f):
        jitted = jax.jit(f, static_argnames=static_argnames + ("interpret",))

        @functools.wraps(f)
        def call(*args, **kwargs):
            return jitted(*args, interpret=get_interpret(), **kwargs)
        return call
    return deco


@_kernel_wrapper((
    "stride", "pad", "relu", "pool", "pool_k", "pool_s", "use_pallas",
    "c_blk", "m_blk", "oh_blk", "b_blk", "groups", "plan", "out_scale",
    "vmem_budget"))
def fused_conv(x, w, b, *, scale=None, out_scale=None, residual=None,
               stride=1, pad=0, relu=True, pool=None, pool_k=2, pool_s=2,
               use_pallas=False, c_blk=LANE, m_blk=LANE, oh_blk=0, b_blk=1,
               groups=1, plan=None, vmem_budget=VMEM_BYTES,
               interpret=False):
    """Fused conv(+bias)(+residual)(+ReLU)(+pool), grouped-conv and
    batch-fold aware.

    ``plan`` (a frozen :class:`repro.kernels.autotune.ConvPlan`) overrides
    the c_blk/m_blk/oh_blk/b_blk knobs with an autotuned point; being
    hashable it rides through jit as a static argument. ``vmem_budget``
    is the VMEM the plan was tuned under: the kernel makes the conv's
    halo in VMEM where that still fits it.

    ``scale`` selects the int8 path: x/w are int8 codes, ``scale`` the
    (M,) combined s_x*s_w requantize multiplier, and ``out_scale``
    (static float) quantizes the output for the next layer (None emits
    fp32). Its non-pallas path is the EXACT int32 reference
    (``quant.ref.conv_int8_ref``), bit-equal to the kernel.

    ``residual`` (B, OH, OW, M) is summed into the conv's output after
    the bias and before the ReLU, in the kernel's epilogue (ResNet's
    shortcut add); it takes no pool and no int8.
    """
    if residual is not None and (pool is not None or scale is not None):
        raise ValueError("fused_conv's residual epilogue takes no pool and "
                         "no int8 scale")
    if plan is not None:
        c_blk, m_blk, oh_blk = plan.c_blk, plan.m_blk, plan.oh_blk
        b_blk = plan.b_blk
    if use_pallas:
        return conv_pipe(x, w, b, scale=scale, out_scale=out_scale,
                         stride=stride, pad=pad, relu=relu, pool=pool,
                         pool_k=pool_k, pool_s=pool_s, c_blk=c_blk,
                         m_blk=m_blk, oh_blk=oh_blk, b_blk=b_blk,
                         groups=groups, vmem_budget=vmem_budget,
                         residual=residual, interpret=interpret)
    if scale is not None:
        return quant_ref.conv_int8_ref(x, w, b, scale, stride=stride,
                                       pad=pad, relu=relu, pool=pool,
                                       pool_k=pool_k, pool_s=pool_s,
                                       groups=groups, out_scale=out_scale)
    return ref.conv_pipe_ref(x, w, b, stride=stride, pad=pad, relu=relu,
                             pool=pool, pool_k=pool_k, pool_s=pool_s,
                             groups=groups, residual=residual)


@_kernel_wrapper(("use_pallas", "exact"))
def lrn(x, *, use_pallas=False, exact=False, interpret=False):
    if exact or not use_pallas:
        return ref.lrn_ref(x)
    return lrn_pwl(x, interpret=interpret)


@_kernel_wrapper(("relu", "use_pallas", "bm", "bn", "bk", "out_scale"))
def fc(x, w, b=None, *, scale=None, out_scale=None, relu=False,
       use_pallas=False, bm=128, bn=128, bk=128, interpret=False):
    """Batched FC: ``x @ w + b`` (+ReLU). ``scale``/``out_scale`` select
    the int8 path as in :func:`fused_conv` (int32 accumulation, the
    requantize epilogue; the exact int32 reference off the kernel)."""
    if use_pallas:
        if b is None:
            b = jnp.zeros((w.shape[1],), x.dtype)
        return matmul_pipe(x, w, b, scale=scale, out_scale=out_scale,
                           relu=relu, bm=bm, bn=bn, bk=bk,
                           interpret=interpret)
    if scale is not None:
        return quant_ref.fc_int8_ref(x, w, b, scale, relu=relu,
                                     out_scale=out_scale)
    return ref.matmul_pipe_ref(x, w, b, relu=relu)


@_kernel_wrapper(("use_pallas", "bq", "bk"))
def attention(q, k, v, *, use_pallas=False, bq=128, bk=128,
              interpret=False):
    """Causal attention, GQA-aware: q (B,Hq,S,D), k/v (B,Hkv,S,D)."""
    g = q.shape[1] // k.shape[1]
    if g > 1:                                  # expand kv heads for the MHA kernel
        k = jnp.repeat(k, g, axis=1)
        v = jnp.repeat(v, g, axis=1)
    if use_pallas:
        return flash_attention(q, k, v, bq=bq, bk=bk,
                               interpret=interpret)
    return ref.flash_attention_ref(q, k, v)
