"""Partition a CNN into balanced pipeline stages.

PipeCNN's cascade works because every kernel stage stays busy; at
cluster scale the same requirement becomes *stage balance* — the GPipe
round time is ``(M + S - 1) * max_s t_stage``, so the slowest stage sets
fleet throughput. This module slices ``models.cnn.fuse_plan`` groups
(the indivisible fused conv(+pool) launches, standalone LRN/pool, FC
layers) into S contiguous chunks minimizing the maximum modeled stage
time, using the SAME per-layer roofline cost model the autotuner ranks
plans with:

  * conv groups — the tuned :class:`~repro.kernels.autotune.ConvPlan`'s
    ``t_model`` (per image, times the microbatch);
  * fc layers — the dtype-aware GEMM DSE
    (:func:`~repro.kernels.autotune.get_gemm_plan`);
  * standalone pool / LRN — bandwidth-bound read+write traffic over the
    HBM roofline (they do negligible math).

The exact min-max contiguous partition is solved by dynamic programming
(group counts are ~16, stages <= 8 — trivially small). Each stage keeps
the plans its groups were costed with: they are the tilings the pipeline
stages run at the microbatch.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

from repro.core.config import (CNNConfig, SpecError, group_inputs,
                               layer_shapes)
from repro.core.roofline import HBM_BW, VMEM_BYTES, pipeline_bubble_fraction
from repro.kernels.autotune import (_DTYPE_BYTES, ConvShape, GemmShape,
                                    get_gemm_plan, get_plan)
from repro.models.cnn import fuse_plan


def group_io_shapes(cfg: CNNConfig) -> List[Tuple[Tuple[int, ...],
                                                  Tuple[int, ...],
                                                  Tuple[int, ...]]]:
    """Per fusion group: ``(group, in_shape, out_shape)`` per image.

    Shapes are per-image activations: ``(H, W, C)`` between spatial
    stages, ``(features,)`` after an FC layer. These are the stage
    boundary shapes the engine's canonical flat buffer must hold. A
    group's input is the layer its first layer reads (in a layer graph,
    not always the group before it).
    """
    shapes = layer_shapes(cfg)

    def io(i: int) -> Tuple[int, ...]:
        fc = i >= 0 and cfg.layers[i].kind == "fc"
        return shapes[i][-1:] if fc else shapes[i]

    return [(group, io(group_inputs(cfg.layers, group)[0]), io(group[-1]))
            for group in fuse_plan(cfg)]


def conv_shape(cfg: CNNConfig, group: Tuple[int, ...],
               in_shape: Tuple[int, ...], dtype: str,
               batch: int) -> ConvShape:
    """The tuning key of a conv fusion group: the conv, with the pool or
    the residual add fused into its epilogue."""
    l = cfg.layers[group[0]]
    h, w, c = in_shape
    tail = cfg.layers[group[1]] if len(group) == 2 else None
    pool = tail if tail is not None and tail.kind == "pool" else None
    return ConvShape(
        h=h, w=w, c=c, kh=l.kernel, kw=l.kernel, m=l.out_ch,
        stride=l.stride, pad=l.pad, groups=l.groups,
        pool=(pool.pool if pool else None),
        pool_k=(pool.kernel if pool else 2),
        pool_s=(pool.stride if pool else 2), dtype=dtype, b=batch,
        residual=tail is not None and tail.kind == "add")


def group_cost(cfg: CNNConfig, group: Tuple[int, ...],
               in_shape: Tuple[int, ...], out_shape: Tuple[int, ...],
               batch: int, *, dtype: Optional[str] = None) -> float:
    """Modeled seconds to run one fusion group over ``batch`` images."""
    return _plan_and_cost(cfg, group, in_shape, out_shape, batch,
                          dtype=dtype)[1]


def _plan_and_cost(cfg: CNNConfig, group: Tuple[int, ...],
                   in_shape: Tuple[int, ...], out_shape: Tuple[int, ...],
                   batch: int, *, dtype: Optional[str] = None
                   ) -> Tuple[Any, float]:
    """The group's tuned plan (None for pool/LRN) and its modeled
    seconds over ``batch`` images."""
    dtype = dtype or cfg.dtype
    dt = _DTYPE_BYTES.get(dtype, 4)
    l = cfg.layers[group[0]]
    if l.kind == "conv":
        shape = conv_shape(cfg, group, in_shape, dtype, batch)
        plan = get_plan(shape, vmem_budget=cfg.vmem_budget)
        return plan, plan.t_model * batch
    if l.kind == "fc":
        k = 1
        for d in in_shape:
            k *= d
        gp = get_gemm_plan(GemmShape(m=batch, k=k, n=out_shape[-1],
                                     dtype=dtype),
                           vmem_budget=cfg.vmem_budget)
        return gp, gp.t_model
    # standalone pool / LRN: bandwidth-bound (read in, write out); LRN
    # runs off the fixed-point pipeline, so its traffic is fp32 always
    n_in = n_out = 1
    for d in in_shape:
        n_in *= d
    for d in out_shape:
        n_out *= d
    el = 4 if l.kind == "lrn" else dt
    return None, batch * (n_in + n_out) * el / HBM_BW


@dataclass(frozen=True)
class Stage:
    """One pipeline stage: a contiguous run of fusion groups."""
    groups: Tuple[Tuple[int, ...], ...]
    in_shape: Tuple[int, ...]          # per-image boundary entering
    out_shape: Tuple[int, ...]
    t_model: float                     # modeled seconds per microbatch
    plans: Tuple[Any, ...] = ()        # per group: ConvPlan/GemmPlan/None


@dataclass(frozen=True)
class StagePlan:
    """A balanced S-way slicing of the network."""
    stages: Tuple[Stage, ...]
    batch: int                         # images per stage invocation
    dtype: str

    @property
    def n_stages(self) -> int:
        return len(self.stages)

    @property
    def t_stage_max(self) -> float:
        return max(s.t_model for s in self.stages)

    @property
    def t_sum(self) -> float:
        return sum(s.t_model for s in self.stages)

    @property
    def balance(self) -> float:
        """mean/max stage time — 1.0 is a perfectly level pipeline."""
        return self.t_sum / (self.n_stages * self.t_stage_max)

    def group_plans(self) -> Dict[Tuple[int, ...], Any]:
        """Every conv/fc group's plan at the stage batch — the tilings
        the pipeline-parallel forward runs."""
        return {g: p for s in self.stages for g, p in zip(s.groups, s.plans)
                if p is not None}

    def max_boundary_elems(self) -> int:
        """Largest per-image activation crossing any stage boundary (or
        entering/leaving the network) — sizes the engine's flat buffer."""
        best = 0
        for s in self.stages:
            for shape in (s.in_shape, s.out_shape):
                n = 1
                for d in shape:
                    n *= d
                best = max(best, n)
        return best

    def round_time(self, n_microbatches: int) -> float:
        """Modeled fill-drain round: (M + S - 1) * t_stage_max."""
        return (n_microbatches + self.n_stages - 1) * self.t_stage_max

    def bubble(self, n_microbatches: int) -> float:
        return pipeline_bubble_fraction(self.n_stages, n_microbatches)


def _min_max_partition(costs: List[float], k: int) -> List[int]:
    """Boundaries of the contiguous k-partition minimizing the max chunk
    sum (exact DP). Returns chunk start indices (len k, first is 0)."""
    n = len(costs)
    prefix = [0.0]
    for c in costs:
        prefix.append(prefix[-1] + c)

    # dp[j][i] = best max-sum splitting costs[:i] into j chunks
    INF = float("inf")
    dp = [[INF] * (n + 1) for _ in range(k + 1)]
    cut = [[0] * (n + 1) for _ in range(k + 1)]
    dp[0][0] = 0.0
    for j in range(1, k + 1):
        for i in range(j, n + 1):
            for s in range(j - 1, i):
                cand = max(dp[j - 1][s], prefix[i] - prefix[s])
                if cand < dp[j][i]:
                    dp[j][i] = cand
                    cut[j][i] = s
    bounds = []
    i = n
    for j in range(k, 0, -1):
        s = cut[j][i]
        bounds.append(s)
        i = s
    return bounds[::-1]


def plan_stages(cfg: CNNConfig, n_stages: int, *, batch: int = 1,
                dtype: Optional[str] = None) -> StagePlan:
    """Slice the network into ``n_stages`` roofline-balanced stages.

    ``batch`` is the microbatch size flowing through each stage (stage
    costs — and the conv/GEMM plans they come from — are tuned at that
    batch). Fusion groups are indivisible, so ``n_stages`` must not
    exceed the group count.
    """
    dtype = dtype or cfg.dtype
    if cfg.is_graph and n_stages > 1:
        raise SpecError(
            "CNNConfig.pp_stages",
            f"{n_stages} pipeline stages pass one activation between "
            f"them, and {cfg.name!r} is a layer graph")
    shapes = group_io_shapes(cfg)
    if n_stages < 1 or n_stages > len(shapes):
        raise ValueError(
            f"n_stages={n_stages} not in [1, {len(shapes)}] "
            f"(the network has {len(shapes)} indivisible fusion groups)")
    tuned = [_plan_and_cost(cfg, g, i, o, batch, dtype=dtype)
             for g, i, o in shapes]
    costs = [c for _, c in tuned]
    starts = _min_max_partition(costs, n_stages)
    stages = []
    for si, s in enumerate(starts):
        e = starts[si + 1] if si + 1 < len(starts) else len(shapes)
        chunk = shapes[s:e]
        stages.append(Stage(
            groups=tuple(g for g, _, _ in chunk),
            in_shape=chunk[0][1], out_shape=chunk[-1][2],
            t_model=sum(costs[s:e]),
            plans=tuple(p for p, _ in tuned[s:e])))
    return StagePlan(stages=tuple(stages), batch=batch, dtype=dtype)


def total_cost(cfg: CNNConfig, batch: int, *,
               dtype: Optional[str] = None) -> float:
    """Modeled seconds for one replica to serve a ``batch`` micro-batch
    (the sum of all group costs — the DP-mode service time)."""
    return sum(group_cost(cfg, g, i, o, batch, dtype=dtype)
               for g, i, o in group_io_shapes(cfg))
