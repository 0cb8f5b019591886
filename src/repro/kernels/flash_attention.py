"""flash_attention — online-softmax attention as a Pallas TPU kernel.

The LM-side instance of the PipeCNN dataflow: the (Sq x Sk) score matrix is
the "inter-stage channel payload" — it exists only tile-by-tile in VMEM,
never in HBM. fp32 running max / normalizer / accumulator live in VMEM
scratch across the KV-tile grid axis (arbitrary semantics).

MHA layout (B, H, S, D); the GQA adaptation lives in ops.py. Causal only.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.mode import resolve_interpret

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  bq: int, bk: int, n_k: int, scale: float):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32) * scale       # (BQ, D)
    k = k_ref[0].astype(jnp.float32)               # (BK, D)
    v = v_ref[0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (BQ, BK)
    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    s = jnp.where(q_pos >= k_pos, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    p = jnp.exp(s - m_new[:, None])
    coef = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * coef + jnp.sum(p, axis=1)
    acc_ref[...] = acc_ref[...] * coef[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ki == n_k - 1)
    def _epilogue():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)[:, None]).astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    bq: int = 128, bk: int = 128,
                    interpret: Optional[bool] = None) -> jax.Array:
    """Causal MHA. q/k/v (B, H, S, D) -> (B, H, S, D)."""
    interpret = resolve_interpret(interpret)
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    bq, bk = min(bq, Sq), min(bk, Sk)
    assert Sq % bq == 0 and Sk % bk == 0
    scale = 1.0 / np.sqrt(D)
    qf = q.reshape(B * H, Sq, D)
    kf = k.reshape(B * H, Sk, D)
    vf = v.reshape(B * H, Sk, D)
    grid = (B * H, Sq // bq, Sk // bk)

    kern = functools.partial(_flash_kernel, bq=bq, bk=bk, n_k=grid[2],
                             scale=scale)
    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda bi, qi, ki: (bi, qi, 0)),
            pl.BlockSpec((1, bk, D), lambda bi, qi, ki: (bi, ki, 0)),
            pl.BlockSpec((1, bk, D), lambda bi, qi, ki: (bi, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda bi, qi, ki: (bi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(B, H, Sq, D)
