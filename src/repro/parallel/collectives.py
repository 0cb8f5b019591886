"""Explicit-collective building blocks (shard_map): the hillclimb levers.

GSPMD's auto-chosen collectives are the baseline; these functions let the
perf loop REPLACE the hot ones:

* ``ring_collective_matmul`` — all-gather x matmul overlap: instead of
  gathering the full LHS then multiplying, each ring step multiplies the
  resident shard while the next shard is in flight (ppermute). This is the
  classic TP latency-hiding trick; on TPU the ppermute maps to neighbor ICI
  hops. (PipeCNN analogue: MemRD streams the next tile while the CU
  computes the current one.)

* ``sp_decode_attention`` — sequence-parallel decode attention with an
  explicit two-scalar (m, l) online-softmax combine over the KV shards,
  replacing GSPMD's all-gather-softmax resolution.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def ring_matmul_overlapped(x: jax.Array, w: jax.Array, mesh: Mesh,
                           axis: str = "model") -> jax.Array:
    """y = x @ w, x batch-sharded is gathered ring-wise and overlapped.

    x: (M, K) sharded (axis, None) — M divided over the ring.
    w: (K, N) sharded (None, axis) — N divided (Megatron column parallel).
    Output: (M, N/n) per shard -> logical (M, N) sharded (None, axis).
    Equivalent to all-gather(x) @ w_local but pipelined per ring step.
    """
    n = mesh.shape[axis]

    def body(x_loc, w_loc):
        idx = jax.lax.axis_index(axis)
        m_blk = x_loc.shape[0]
        M = m_blk * n
        out = jnp.zeros((M, w_loc.shape[1]), jnp.float32)
        blk = x_loc

        def step(i, carry):
            out, blk = carry
            # after i rotations of the (j+1 -> j) ring, this device holds
            # the block originally owned by shard (idx + i)
            src = (idx + i) % n
            part = jnp.dot(blk, w_loc, preferred_element_type=jnp.float32)
            out = jax.lax.dynamic_update_slice_in_dim(
                out, part, src * m_blk, 0)
            blk = jax.lax.ppermute(
                blk, axis, [((j + 1) % n, j) for j in range(n)])
            return out, blk

        out, _ = jax.lax.fori_loop(0, n, step, (out, blk))
        return out.astype(x_loc.dtype)

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(axis, None), P(None, axis)),
        out_specs=P(None, axis),
        check_vma=False)(x, w)


def sp_decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                        pos: jax.Array, mesh: Mesh, axis: str = "model",
                        ) -> jax.Array:
    """Sequence-parallel one-token attention with explicit (m, l) combine.

    q: (B, H, D) replicated over ``axis``; k/v_cache: (B, S, H, D) with S
    sharded over ``axis``. Each shard computes partial online-softmax stats
    over its sequence slice; the combine moves only (B,H) scalars + (B,H,D)
    vectors — versus GSPMD's default which may all-gather score rows.
    """
    n = mesh.shape[axis]
    S = k_cache.shape[1]
    s_loc = S // n
    scale = 1.0 / np.sqrt(q.shape[-1])

    def body(q, kc, vc, pos):
        idx = jax.lax.axis_index(axis)
        kpos = idx * s_loc + jnp.arange(s_loc)
        s = jnp.einsum("bhd,bshd->bhs", q.astype(jnp.float32),
                       kc.astype(jnp.float32)) * scale
        s = jnp.where((kpos <= pos)[None, None, :], s, -1e30)
        m = jnp.max(s, axis=-1)                                 # (B,H)
        p = jnp.exp(s - m[..., None])
        l = jnp.sum(p, axis=-1)
        o = jnp.einsum("bhs,bshd->bhd", p, vc.astype(jnp.float32))
        # combine partial softmax stats across shards
        m_g = jax.lax.pmax(m, axis)
        corr = jnp.exp(m - m_g)
        l_g = jax.lax.psum(l * corr, axis)
        o_g = jax.lax.psum(o * corr[..., None], axis)
        return (o_g / jnp.maximum(l_g, 1e-30)[..., None]).astype(q.dtype)

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(None, None, None), P(None, axis, None, None),
                  P(None, axis, None, None), P()),
        out_specs=P(None, None, None))(q, k_cache, v_cache, pos)
