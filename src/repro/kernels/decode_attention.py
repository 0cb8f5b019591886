"""decode_attention — one-token GQA attention over a KV cache, fused.

The §Perf decode analysis (EXPERIMENTS.md cell 3) showed the pure-XLA
decode step pays a functional read+write of the full cache slice per layer
(masked where-update) plus separate score/softmax/PV passes. This kernel is
the TPU-native fix: ONE `pallas_call` that

  * updates the cache in place at position `pos`
    (``input_output_aliased`` — no copy, the paper's MemWR writes once),
  * streams KV tiles HBM->VMEM once, computing the online-softmax
    numerator/denominator on the fly (scores never leave VMEM — the
    Conv->Pool channel, again),
  * emits the attention output and the updated cache views.

Grid: (batch, kv_heads, S_tiles) with running (m, l, acc) scratch over the
S axis — the same accumulate-epilogue structure as conv_pipe/matmul_pipe
(PipeCNN's single multi-mode engine).

Layout: q (B, HKV, G, D); cache (B, S, HKV, D). Causal over `pos`.
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


def _decode_kernel(pos_ref, q_ref, newk_ref, newv_ref, k_ref, v_ref,
                   ok_ref, ov_ref, o_ref, m_ref, l_ref, acc_ref, *,
                   bs: int, n_s: int, scale: float):
    si = pl.program_id(2)
    pos = pos_ref[0]

    @pl.when(si == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # cache tile (may contain the slot being written this step)
    k = k_ref[0, :, 0, :]                          # (BS, D)
    v = v_ref[0, :, 0, :]
    s_pos = si * bs + jax.lax.broadcasted_iota(jnp.int32, (bs,), 0)
    at_pos = (s_pos == pos)[:, None]
    k = jnp.where(at_pos, newk_ref[0, 0][None, :], k)   # in-place update
    v = jnp.where(at_pos, newv_ref[0, 0][None, :], v)
    ok_ref[0, :, 0, :] = k.astype(ok_ref.dtype)
    ov_ref[0, :, 0, :] = v.astype(ov_ref.dtype)

    q = q_ref[0, 0].astype(jnp.float32) * scale    # (G, D)
    s = jax.lax.dot_general(q, k.astype(jnp.float32),
                            (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (G, BS)
    s = jnp.where((s_pos <= pos)[None, :], s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    p = jnp.exp(s - m_new[:, None])
    coef = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * coef + jnp.sum(p, axis=1)
    acc_ref[...] = acc_ref[...] * coef[:, None] + jax.lax.dot_general(
        p, v.astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(si == n_s - 1)
    def _epilogue():
        o_ref[0, 0] = (acc_ref[...]
                       / jnp.maximum(l_ref[...], 1e-30)[:, None]
                       ).astype(o_ref.dtype)


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     new_k: jax.Array, new_v: jax.Array, pos: jax.Array, *,
                     bs: int = 512,
                     interpret: Optional[bool] = None):
    """Fused decode attention + in-place cache update.

    q: (B, HKV, G, D); k/v_cache: (B, S, HKV, D); new_k/v: (B, HKV, D);
    pos: scalar int32. Returns (o (B, HKV, G, D), new_k_cache, new_v_cache).
    On TPU the cache update aliases the input buffers (no copy).
    """
    interpret = resolve_interpret(interpret)
    B, S, HKV, D = k_cache.shape
    G = q.shape[2]
    bs = min(bs, S)
    assert S % bs == 0
    grid = (B, HKV, S // bs)
    kern = functools.partial(_decode_kernel, bs=bs, n_s=grid[2],
                             scale=1.0 / np.sqrt(D))
    pos_arr = jnp.asarray(pos, jnp.int32).reshape(1)

    out_shapes = (
        jax.ShapeDtypeStruct(k_cache.shape, k_cache.dtype),   # updated k
        jax.ShapeDtypeStruct(v_cache.shape, v_cache.dtype),   # updated v
        jax.ShapeDtypeStruct((B, HKV, G, D), q.dtype),        # attn out
    )
    o_k, o_v, out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            # pos: tiny scalar block (on real TPU: scalar-prefetch/SMEM)
            pl.BlockSpec((1,), lambda b, h, s: (0,)),
            pl.BlockSpec((1, 1, G, D), lambda b, h, s: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, D), lambda b, h, s: (b, h, 0)),
            pl.BlockSpec((1, 1, D), lambda b, h, s: (b, h, 0)),
            pl.BlockSpec((1, bs, 1, D), lambda b, h, s: (b, s, h, 0)),
            pl.BlockSpec((1, bs, 1, D), lambda b, h, s: (b, s, h, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bs, 1, D), lambda b, h, s: (b, s, h, 0)),
            pl.BlockSpec((1, bs, 1, D), lambda b, h, s: (b, s, h, 0)),
            pl.BlockSpec((1, 1, G, D), lambda b, h, s: (b, h, 0, 0)),
        ],
        out_shape=list(out_shapes),
        scratch_shapes=[
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G, D), jnp.float32),
        ],
        input_output_aliases={4: 0, 5: 1},     # cache updated in place
        interpret=interpret,
    )(pos_arr, q, new_k, new_v, k_cache, v_cache)
    return out, o_k, o_v
