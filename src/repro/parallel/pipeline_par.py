"""GPipe-style pipeline parallelism over a mesh axis (shard_map + ppermute).

For the multi-pod mesh the "pod" axis can carry pipeline stages instead of
pure DP: stage s holds layers [s*L/S, (s+1)*L/S); microbatches stream
through, activations crossing stages via collective_permute (one ICI/DCN
hop). This is the PipeCNN cascade at cluster scale — each pod is a pipeline
stage, the inter-pod link is the channel.

The schedule below is the classic fill-drain GPipe loop with M microbatches
over S stages (bubble fraction (S-1)/(M+S-1)); it runs forward-only
(serving / evaluation) or in a gradient context via jax.grad over the whole
scheduled computation.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def _gpipe_schedule(apply: Callable, micro_local: jax.Array, idx,
                    S: int, n_microbatches: int, axis: str) -> jax.Array:
    """The fill-drain tick loop shared by both pipeline entry points.

    ``apply(h) -> h`` runs THIS device's stage (already bound to its
    stage index/params); activations must keep one fixed shape across
    stages — heterogeneous stages flatten into a canonical buffer
    (:func:`pipeline_forward_stages`).
    """
    n_ticks = n_microbatches + S - 1
    fwd = [(i, (i + 1) % S) for i in range(S)]         # stage i -> i+1

    def tick(t, carry):
        outputs, inflight = carry
        # which microbatch enters stage 0 at tick t?
        mb_idx = jnp.clip(t, 0, n_microbatches - 1)
        incoming = jnp.where(
            (idx == 0) & (t < n_microbatches),
            jax.lax.dynamic_index_in_dim(micro_local, mb_idx, 0, False),
            inflight)
        h = apply(incoming)
        # last stage: record finished microbatch (entered at t-S+1)
        done_idx = jnp.clip(t - S + 1, 0, n_microbatches - 1)
        outputs = jnp.where(
            (idx == S - 1) & (t >= S - 1),
            jax.lax.dynamic_update_index_in_dim(outputs, h, done_idx, 0),
            outputs)
        inflight = jax.lax.ppermute(h, axis, fwd)
        return outputs, inflight

    outputs = jnp.zeros_like(micro_local)
    inflight = jnp.zeros_like(micro_local[0])
    outputs, _ = jax.lax.fori_loop(0, n_ticks, tick, (outputs, inflight))
    # broadcast final outputs from the last stage to all pods
    # (ppermute is a permutation — use a masked psum to broadcast)
    return jax.lax.psum(
        jnp.where(idx == S - 1, outputs, jnp.zeros_like(outputs)), axis)


def pipeline_forward(stage_fn: Callable, params_stacked, x: jax.Array,
                     mesh: Mesh, axis: str = "pod",
                     n_microbatches: int = 4) -> jax.Array:
    """Run x through S pipeline stages laid along ``axis``.

    stage_fn(stage_params, h) -> h   applies one stage's layers.
    params_stacked: pytree with leading dim S (stage-major), sharded so
    stage s's slice lives on pod s.  x: (B, ...) batch-shardable into
    n_microbatches along dim 0.
    """
    S = mesh.shape[axis]
    B = x.shape[0]
    assert B % n_microbatches == 0
    mb = B // n_microbatches
    micro = x.reshape(n_microbatches, mb, *x.shape[1:])

    def body(params_local, micro_local):
        # params_local: this stage's params (leading dim 1) on this shard
        stage_params = jax.tree.map(lambda a: a[0], params_local)
        idx = jax.lax.axis_index(axis)
        return _gpipe_schedule(lambda h: stage_fn(stage_params, h),
                               micro_local, idx, S, n_microbatches, axis)

    out = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(axis), P()),         # params stage-sharded; x replicated
        out_specs=P(),
        check_vma=False)(params_stacked, micro)
    return out.reshape(B, *x.shape[1:])


def pipeline_forward_stages(stage_fn: Callable, x: jax.Array, mesh: Mesh,
                            axis: str = "pipe", n_microbatches: int = 4,
                            dp_axis: str = None) -> jax.Array:
    """Heterogeneous-stage pipeline: ``stage_fn(stage_idx, h) -> h``.

    The CNN serving entry point: unlike :func:`pipeline_forward` (uniform
    stages, stage-stacked params), CNN stages change activation SHAPE
    (H shrinks, C grows, FC flattens), so the caller flattens activations
    into a fixed-size canonical buffer and ``stage_fn`` dispatches on the
    traced stage index (``jax.lax.switch`` over per-stage branches with
    static interior shapes — see ``repro.serve.engine``). ``h`` must keep
    one shape/dtype across stages.

    ``dp_axis`` composes data parallelism with the pipeline on a 2-D
    mesh: the per-microbatch row dim is sharded over ``dp_axis`` (each
    data shard streams its rows through the same device-resident stages)
    while activations hop stages over ``axis`` — DP x PP in ONE
    shard_map, the engine's hybrid mode.
    """
    S = mesh.shape[axis]
    B = x.shape[0]
    assert B % n_microbatches == 0
    mb = B // n_microbatches
    micro = x.reshape(n_microbatches, mb, *x.shape[1:])

    def body(micro_local):
        idx = jax.lax.axis_index(axis)
        return _gpipe_schedule(lambda h: stage_fn(idx, h),
                               micro_local, idx, S, n_microbatches, axis)

    spec = P(None, dp_axis) if dp_axis else P()
    out = jax.shard_map(body, mesh=mesh, in_specs=(spec,), out_specs=spec,
                    check_vma=False)(micro)
    return out.reshape(B, *x.shape[1:])
