"""Per-layer design-space exploration for the tiled conv pipeline.

The paper tunes its two throughput parameters (VEC_SIZE, CU_NUM) with an
offline sweep against the DE5-net's DSP budget and DDR roofline (Fig. 7).
This module is that sweep for the TPU kernel, with two more axes: the
line-buffer depth ``oh_blk`` introduced by spatial tiling, and the
images-per-grid-step ``b_blk`` introduced by batch folding (the serving
path). ``b_blk`` trades VMEM (the x tile and accumulator scale with it)
against weight-fetch amortization — the paper's batch-64 FC argument
applied to conv: one weight tile DMA feeds ``b_blk`` images. All scores
are per image, so plans tuned at different serve batches are comparable.

  * :func:`conv_vmem_bytes` — analytic VMEM working-set model of one
    ``conv_pipe`` grid step (the feasibility constraint; VMEM is the TPU's
    "DSP count").
  * :func:`enumerate_plans` — all legal ``(b_blk, c_blk, m_blk, oh_blk)``
    points under a VMEM budget.
  * :func:`score_plan` — roofline cost model (``core.roofline.time_bounds``):
    MXU-utilization-scaled compute vs. the DMA traffic the BlockSpec index
    maps actually generate (x is re-fetched once per M-tile, w once per
    (batch, H-tile), halo rows are re-fetched once per neighbouring tile).
  * :func:`get_plan` — pick the best-scoring feasible plan, memoised in a
    process-wide registry keyed by ``(layer shape, dtype, backend)``.
  * :func:`measure_plan` / :func:`measure_gemm_plan` — wall-clock
    seconds/call for one plan, the primitive ``repro.obs.profiler`` builds
    its measured-refinement pass from. Operand data is deterministic per
    ``(shape, plan)`` (crc32-derived key, split into independent x/w
    streams) and the ``interpret`` mode defaults to the process backend
    mode (``mode.get_interpret()``) so a measurement is taken — and
    recorded in provenance — in the mode that will actually run.

Plans are plain frozen dataclasses so they can ride through ``jax.jit``
static arguments, and the registry serialises to JSON for the benchmark
trajectory file (``BENCH_conv.json``).
"""
from __future__ import annotations

import contextlib
import dataclasses
import warnings
from dataclasses import dataclass, field
from typing import Dict, Iterator, List, Optional, Tuple

from repro.core.roofline import (MXU_DIM, VMEM_BYTES, mxu_utilization,
                                 time_bounds)
from repro.kernels.conv_pipe import (LANE, SUBLANE, _round_up,
                                     contraction_block, s2d_geometry)
from repro.kernels.mode import resolve_interpret

_DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2, "int8": 1}

# The tuner prices plans for one device, the TPU v5e
# (core.roofline.TARGET_DEVICE_KIND: its peaks there, its VMEM layout
# here). The plans it proposes lower there.


def _sublanes(dt: int) -> int:
    """Rows of one (sublane, 128) VMEM tile: 8 for 32-bit dtypes, 16
    for 16-bit, 32 for int8 (packed rows)."""
    return SUBLANE * 4 // dt


def _tile_bytes(rows: int, cols: int, dt: int) -> int:
    """Bytes a (rows, cols) slab takes in VMEM: Mosaic pads the minor
    dim to 128 lanes and the second-minor to the dtype's sublane tile."""
    return _round_up(rows, _sublanes(dt)) * _round_up(cols, LANE) * dt

# The public autotune surface (pinned by tests/test_api_surface.py).
__all__ = [
    "ConvShape", "ConvPlan", "GemmShape", "GemmPlan",
    "conv_vmem_bytes", "plan_fits", "score_plan", "enumerate_plans",
    "best_plan",
    "gemm_vmem_bytes", "gemm_plan_fits", "score_gemm_plan",
    "enumerate_gemm_plans",
    "best_gemm_plan",
    "measure_plan", "measure_gemm_plan",
    "get_plan", "get_gemm_plan", "plan_for_layer", "gemm_plan_for_layer",
    "clear_registry", "registry_snapshot", "gemm_registry_snapshot",
    "dump_registry", "seed_registry", "record_lookups",
    "sweep_stats", "reset_sweep_stats",
    "measure_stats", "reset_measure_stats", "count_measure_hit",
]


@dataclass(frozen=True)
class ConvShape:
    """Static signature of one conv(+pool) layer — the registry key.

    ``b`` is the serving batch the layer is tuned FOR (part of the cache
    key since PR 2: the best ``b_blk`` depends on it). ``b=1`` keeps the
    per-image plans of PR 1.
    """
    h: int
    w: int
    c: int                      # total input channels (all groups)
    kh: int
    kw: int
    m: int                      # total output channels (all groups)
    stride: int = 1
    pad: int = 0
    groups: int = 1
    pool: Optional[str] = None
    pool_k: int = 2
    pool_s: int = 2
    dtype: str = "float32"
    b: int = 1                  # serving batch (images per launch)
    # a residual operand summed in the epilogue (ResNet's shortcut add).
    # Left out of the repr and, while False, of the serialised row, so a
    # chain's plan rows and registry order read as they always have
    residual: bool = field(default=False, repr=False)

    def to_dict(self) -> dict:
        """The serialised shape of a plan row."""
        d = dataclasses.asdict(self)
        if not self.residual:
            del d["residual"]
        return d

    @property
    def oh(self) -> int:
        return (self.h + 2 * self.pad - self.kh) // self.stride + 1

    @property
    def ow(self) -> int:
        return (self.w + 2 * self.pad - self.kw) // self.stride + 1

    @property
    def macs(self) -> int:
        """Multiply-accumulates per image (grouped conv divides C)."""
        return (self.oh * self.ow * self.m * self.kh * self.kw
                * (self.c // self.groups))


@dataclass(frozen=True)
class ConvPlan:
    """A tuned tiling point. Hashable => usable as a jit static argument."""
    c_blk: int
    m_blk: int
    oh_blk: int
    b_blk: int = 1              # images per grid step (batch folding)
    vmem_bytes: int = 0         # modelled working set (informational)
    t_model: float = 0.0        # modelled roofline time, seconds/image

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


def _conv_geometry(shape: ConvShape, c_blk: int, m_blk: int,
                   oh_blk: int, b_blk: int):
    """The kernel's real geometry for one plan: the space-to-depth conv
    (stride 1, ``s*s*C/G`` channels, times the taps folded into the
    contraction), clamped blocks and the H tiling."""
    g = s2d_geometry(shape.h, shape.w, shape.c // shape.groups, shape.kh,
                     shape.kw, stride=shape.stride, pad=shape.pad)
    c_blk = contraction_block(c_blk, g)
    m_blk = min(m_blk, shape.m // shape.groups)
    b_blk = max(1, min(b_blk, shape.b))
    tiles = g.tiles(oh_blk, pool=shape.pool, pool_k=shape.pool_k,
                    pool_s=shape.pool_s)
    pw = ((g.ow - shape.pool_k) // shape.pool_s + 1
          if shape.pool else g.ow)
    return g, c_blk, m_blk, b_blk, tiles, pw


def conv_vmem_bytes(shape: ConvShape, c_blk: int, m_blk: int,
                    oh_blk: int, b_blk: int = 1,
                    vmem_budget: int = VMEM_BYTES) -> int:
    """VMEM working set of one grid step of the tiled conv_pipe kernel.

    Every slab is counted as Mosaic lays it out: minor dim padded to 128
    lanes, second-minor to the dtype's sublane tile (AlexNet conv1's 48
    space-to-depth channels occupy 128 lanes, its 144 folded ones 256).
    Pipelined refs (x tile, w tile, bias, out tile) are double-buffered by
    Pallas (factor 2); the accumulator scratch (and the pool scratch, when
    pooling) is single-buffered and always 4 bytes/element. The kernel
    body's temporaries are counted too: the column-shifted x window (for
    a folded layer its ``kw`` pieces and their side-by-side
    concatenation), for a row-folded layer the ``kh`` row-shifted pieces
    of that window, the folded im2col patch (VGG-16 conv1_2's 576
    channels take 640 lanes), one tap's matmul result and the fp32
    epilogue value. The x
    tile, out tile, accumulator and temporaries scale with ``b_blk``; the
    weight tile does not — that asymmetry is the whole point of batching.
    int8 shrinks the streamed tiles (1-byte tensors) but bias/scale stay
    fp32. A residual operand streams one output-shaped tile more. Where
    the kernel makes the halo itself within ``vmem_budget``
    (:func:`halo_in_kernel`), the pipelined x block is the unpadded
    image's rows and the padded tile is a single-buffered line buffer.
    """
    return _conv_vmem(shape, c_blk, m_blk, oh_blk, b_blk,
                      halo=halo_in_kernel(shape, c_blk, m_blk, oh_blk,
                                          b_blk, vmem_budget))


def halo_in_kernel(shape: ConvShape, c_blk: int, m_blk: int, oh_blk: int,
                   b_blk: int = 1, vmem_budget: int = VMEM_BYTES) -> bool:
    """Whether ``conv_pipe`` makes this conv's halo (the pad rows and
    columns, the columns out to ``ow_p + kw - 1``, the last tile's rows)
    in VMEM rather than taking a zero-padded copy from the wrapper: a
    stride-1 conv with a halo to make, where the plan's working set with
    the line buffer still fits ``vmem_budget``. So a plan fits a budget
    exactly when it did with the halo in the wrapper. A strided conv's
    halo stays in its space-to-depth copy."""
    if shape.stride != 1:
        return False
    g, _, _, b_blk, tiles, _ = _conv_geometry(shape, c_blk, m_blk, oh_blk,
                                             b_blk)
    n_h, _, _, hp_blk, row_step = tiles
    if not (shape.pad or g.w > shape.w
            or (n_h - 1) * row_step + hp_blk > shape.h):
        return False
    return _conv_vmem(shape, c_blk, m_blk, oh_blk, b_blk,
                      halo=True) <= vmem_budget


def _conv_vmem(shape: ConvShape, c_blk: int, m_blk: int, oh_blk: int,
               b_blk: int, *, halo: bool) -> int:
    """:func:`conv_vmem_bytes` with the halo's place given."""
    dt = _DTYPE_BYTES.get(shape.dtype, 4)
    quantized = shape.dtype == "int8"
    g, c_blk, m_blk, b_blk, tiles, pw = _conv_geometry(
        shape, c_blk, m_blk, oh_blk, b_blk)
    _, pr, oh_ext, hp_blk, _ = tiles
    rows = b_blk * oh_ext * g.ow_p
    cx = c_blk // g.taps_folded                  # channels of the x tile
    cj = c_blk // g.kh_fold                      # of the x window
    x_tile = b_blk * hp_blk * _tile_bytes(g.w, cx, dt)
    # the halo made in VMEM: the x block DMA'd is the image's own rows and
    # columns, copied into the padded x tile (the line buffer)
    x_block = (b_blk * min(hp_blk, shape.h) * _tile_bytes(shape.w, cx, dt)
               if halo else x_tile)
    w_tile = g.kh * g.kw * _tile_bytes(c_blk, m_blk, dt)
    vec = _tile_bytes(1, m_blk, 4)               # fp32 (1, M_BLK) row
    o_tile = b_blk * pr * _tile_bytes(pw, m_blk, dt)
    acc = _tile_bytes(rows, m_blk, 4)            # fp32 / int32 scratch
    pool = (b_blk * oh_ext * _tile_bytes(g.ow_p, m_blk, 4)
            if shape.pool else 0)
    pieces = g.kw_fold if g.kw_fold > 1 else 0
    row_pieces = g.kh_fold if g.kh_fold > 1 else 0
    temps = (b_blk * hp_blk * (_tile_bytes(g.ow_p, cj, dt)     # x window
                               + pieces * _tile_bytes(g.ow_p, cx, dt))
             + row_pieces * b_blk * oh_ext * _tile_bytes(g.ow_p, cj, dt)
             + _tile_bytes(rows, c_blk, dt)                    # patch
             + 2 * acc)                        # tap result + epilogue
    pipelined = (x_block + w_tile + vec * (2 if quantized else 1)
                 + o_tile * (2 if shape.residual else 1))
    return (2 * pipelined + acc + pool + temps
            + (x_tile if halo else 0))


def plan_fits(shape: ConvShape, plan: ConvPlan,
              vmem_budget: int = VMEM_BYTES) -> bool:
    """Pure feasibility predicate: does ``plan`` fit ``vmem_budget``?

    The exact constraint :func:`enumerate_plans` applies when it prunes
    the sweep, factored out so static checkers (``repro.analysis``) can
    re-prove feasibility of a committed plan row without running any
    sweep or kernel. No side effects: no registry access, no
    sweep-counter bump.
    """
    return conv_vmem_bytes(shape, plan.c_blk, plan.m_blk, plan.oh_blk,
                           plan.b_blk, vmem_budget) <= vmem_budget


def score_plan(shape: ConvShape, c_blk: int, m_blk: int,
               oh_blk: int, b_blk: int = 1) -> Tuple[float, float]:
    """(t_compute, t_memory) roofline terms PER IMAGE for one plan.

    Models the traffic the BlockSpec index maps actually generate for the
    space-to-depth conv the kernel runs:
      x  — re-fetched for every M-tile; halo rows re-fetched per H-tile
      w  — re-fetched for every (image-block, H-tile): batch folding
           divides the per-image weight traffic by ``b_blk``
      out — written once (and a residual operand read once)
    Channel padding waste (Fig. 7's VEC_SIZE argument) shows up through
    the padded c/m tile counts; batch padding waste (a trailing partial
    image block computes zero images) through the padded image count;
    space-to-depth tap padding and the 8-aligned output width through
    the computed rows and taps.

    Dtype-aware (the paper's fixed-point trade, modeled): int8 shrinks
    every streamed byte 4x vs fp32 AND doubles the MXU op rate
    (``roofline.peak_ops``), so bandwidth-bound layers model at <= 1/4
    and compute-bound layers at 1/2 — the tuner consequently picks
    different (b,c,m,oh)_blk points for int8 than for fp32.
    """
    dt = _DTYPE_BYTES.get(shape.dtype, 4)
    g, c_blk, m_blk, b_blk, tiles, pw = _conv_geometry(
        shape, c_blk, m_blk, oh_blk, b_blk)
    n_h, pr, oh_ext, hp_blk, _ = tiles
    mg = shape.m // shape.groups
    cgp, mgp = _round_up(g.c, c_blk), _round_up(mg, m_blk)
    n_c, n_m = cgp // c_blk, shape.groups * (mgp // m_blk)
    n_b = -(-shape.b // b_blk)
    bp = n_b * b_blk                       # padded image count

    cx = c_blk // g.taps_folded            # channels of the x tile
    x_bytes = bp * n_h * n_m * n_c * hp_blk * g.w * cx * dt
    w_bytes = n_b * n_h * n_m * n_c * g.kh * g.kw * c_blk * m_blk * dt
    o_bytes = (bp * n_h * pr * pw * (n_m * m_blk) * dt
               * (2 if shape.residual else 1))
    # padded-lane compute: the kernel multiplies the padded tiles
    flops = 2 * bp * n_h * oh_ext * g.ow_p * (n_m * m_blk) \
        * g.kh * g.kw * cgp
    tc, tm = time_bounds(flops, x_bytes + w_bytes + o_bytes,
                         mxu_util=mxu_utilization(c_blk, m_blk),
                         dtype=shape.dtype)
    return tc / shape.b, tm / shape.b


def _lane_cands(full: int, cap: int) -> List[int]:
    """Lane-legal block sizes for a minor dim of size ``full``: multiples
    of 128 below it (up to ``cap``) plus the whole dim."""
    return sorted({v for v in range(LANE, min(full, cap + 1), LANE)}
                  | {full})


def _pow2_upto(limit: int, lo: int = 8) -> List[int]:
    vals, v = [], lo
    while v <= limit:
        vals.append(v)
        v *= 2
    if not vals or vals[-1] != limit:
        vals.append(limit)
    return vals


def enumerate_plans(shape: ConvShape,
                    vmem_budget: int = VMEM_BYTES) -> List[ConvPlan]:
    """All (b_blk, c_blk, m_blk, oh_blk) points that fit the VMEM budget
    and lower on the target: channel blocks are lane-legal
    (:func:`repro.kernels.conv_pipe.lane_legal`).

    ``b_blk`` candidates are powers of two up to the serving batch
    ``shape.b`` (plus the batch itself); for b=1 this degenerates to the
    PR 1 three-axis sweep. A residual conv's ``oh_blk`` divides its
    output rows, so its residual operand needs no padded copy.
    """
    g = s2d_geometry(shape.h, shape.w, shape.c // shape.groups, shape.kh,
                     shape.kw, stride=shape.stride, pad=shape.pad)
    # channel blocks are lane dims: multiples of 128 or the whole
    # (space-to-depth, per-group) channel count; one whole tile if folded
    c_cands = sorted({contraction_block(c, g)
                      for c in _lane_cands(g.c, 2 * MXU_DIM)})
    m_cands = _lane_cands(shape.m // shape.groups, 2 * MXU_DIM)
    step = shape.pool_s if shape.pool else 1
    oh_cands = sorted({min(_round_up(v, step), _round_up(shape.oh, step))
                       for v in (1, 2, 4, 8, 16, 32, 64, shape.oh)})
    if shape.residual:
        oh_cands = [v for v in range(1, shape.oh + 1) if shape.oh % v == 0]
    b_cands = sorted({min(v, shape.b) for v in _pow2_upto(shape.b, lo=1)})
    plans = []
    for bb in b_cands:
        for cb in c_cands:
            for mb in m_cands:
                for ob in oh_cands:
                    vmem = conv_vmem_bytes(shape, cb, mb, ob, bb,
                                           vmem_budget)
                    if vmem > vmem_budget:
                        continue
                    tc, tm = score_plan(shape, cb, mb, ob, bb)
                    plans.append(ConvPlan(cb, mb, ob, b_blk=bb,
                                          vmem_bytes=vmem,
                                          t_model=max(tc, tm)))
    return plans


def best_plan(shape: ConvShape,
              vmem_budget: int = VMEM_BYTES) -> ConvPlan:
    """The lowest modelled-time feasible plan (larger tiles break ties —
    fewer grid steps means less per-step launch/DMA fixed cost)."""
    plans = enumerate_plans(shape, vmem_budget)
    if not plans:
        raise ValueError(
            f"no feasible conv plan for {shape} under {vmem_budget} B VMEM")
    return min(plans, key=lambda p: (p.t_model,
                                     -(p.b_blk * p.c_blk * p.m_blk
                                       * p.oh_blk)))


def _measure_seed(shape, plan) -> int:
    """Deterministic PRNG seed per ``(shape, plan)`` measurement point.

    ``zlib.crc32`` of the reprs, NOT python ``hash()`` — string hashing
    is salted per process (PYTHONHASHSEED), and re-measuring the same
    point must benchmark identical operand bytes.
    """
    import zlib
    return zlib.crc32(repr((shape, plan)).encode())


def measure_plan(shape: ConvShape, plan: ConvPlan, *, iters: int = 3,
                 warmup: int = 1,
                 interpret: Optional[bool] = None) -> float:
    """Wall-clock seconds/call for one conv plan (measured refinement).

    ``warmup`` un-timed calls absorb compilation, then ``iters`` timed
    calls are averaged. x and w come from SPLIT streams of one
    crc32-derived key — deterministic per ``(shape, plan)`` and mutually
    independent (a single reused key would correlate the operands).
    Counted in :func:`measure_stats` (``conv_measured``), the measured
    mirror of the ``sweep_stats`` DSE counters.
    """
    import time

    import jax
    import jax.numpy as jnp

    from repro.kernels.conv_pipe import conv_pipe

    interpret = resolve_interpret(interpret)
    kx, kw = jax.random.split(jax.random.key(_measure_seed(shape, plan)))
    x = jax.random.normal(kx, (shape.b, shape.h, shape.w, shape.c),
                          jnp.float32)
    w = jax.random.normal(kw, (shape.kh, shape.kw,
                               shape.c // shape.groups, shape.m),
                          jnp.float32) * 0.1
    b = jnp.zeros((shape.m,))
    qkw = {}
    if shape.dtype == "int8":
        # measure the kernel the plan was tuned for: int8 operands plus a
        # requantize scale, not a float stand-in (the VMEM feasibility was
        # modeled at 1 byte/element)
        from repro.quant.core import (abs_max_scale, quantize,
                                      quantize_channelwise)
        sx = float(abs_max_scale(x))
        w, ws = quantize_channelwise(w, axis=-1)
        x = quantize(x, sx)
        qkw = dict(scale=ws * sx, out_scale=0.05)
    else:
        dt = jnp.float32 if shape.dtype == "float32" else jnp.bfloat16
        x, w, b = x.astype(dt), w.astype(dt), b.astype(dt)
    if shape.residual:
        qkw = dict(residual=jnp.ones((shape.b, shape.oh, shape.ow, shape.m),
                                     x.dtype))

    def run():
        return conv_pipe(x, w, b, stride=shape.stride,
                         pad=shape.pad, pool=shape.pool, pool_k=shape.pool_k,
                         pool_s=shape.pool_s, c_blk=plan.c_blk,
                         m_blk=plan.m_blk, oh_blk=plan.oh_blk,
                         b_blk=plan.b_blk, groups=shape.groups,
                         interpret=interpret, **qkw)

    for _ in range(max(1, warmup)):           # compile / warm up
        run().block_until_ready()
    t0 = time.perf_counter()   # repro: allow[RPA102] the measurement harness
    for _ in range(max(1, iters)):
        run().block_until_ready()
    _MEASURE_STATS["conv_measured"] += 1
    # repro: allow[RPA102] measured seconds/call IS this function's output
    return (time.perf_counter() - t0) / max(1, iters)


# ---------------------------------------------------------------------------
# GEMM design-space exploration (the FC / classifier side of the engine)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class GemmShape:
    """Static signature of one batched-FC GEMM — the registry key.

    ``m`` is the row count (the serving micro-batch), ``k``/``n`` the
    contraction/output features; ``dtype`` the COMPUTE dtype (int8 FC
    plans differ from fp32 ones, closing the ROADMAP item "int8 FC plans
    are untuned").
    """
    m: int
    k: int
    n: int
    dtype: str = "float32"

    @property
    def macs(self) -> int:
        return self.m * self.k * self.n

    def to_dict(self) -> dict:
        """The serialised shape of a plan row."""
        return dataclasses.asdict(self)


@dataclass(frozen=True)
class GemmPlan:
    """A tuned (bm, bn, bk) blocking for ``matmul_pipe``. Hashable, so it
    rides through jit static arguments like :class:`ConvPlan`."""
    bm: int
    bn: int
    bk: int
    vmem_bytes: int = 0         # modelled working set (informational)
    t_model: float = 0.0        # modelled roofline time, seconds/call

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


def gemm_vmem_bytes(shape: GemmShape, bm: int, bn: int, bk: int) -> int:
    """VMEM working set of one ``matmul_pipe`` grid step.

    Slabs are counted with Mosaic's (sublane, 128) padding. Pipelined
    refs (x, w, bias, out) are double-buffered; the accumulator scratch
    is single-buffered and always 4 bytes/element (fp32, or int32 in the
    int8 mode), and the tile's matmul result is a temporary of the same
    size. int8 keeps an fp32 bias and adds the fp32 requantize-scale
    row — the same asymmetry as :func:`conv_vmem_bytes`.
    """
    dt = _DTYPE_BYTES.get(shape.dtype, 4)
    quantized = shape.dtype == "int8"
    bm, bn, bk = min(bm, shape.m), min(bn, shape.n), min(bk, shape.k)
    x_t = _tile_bytes(bm, bk, dt)
    w_t = _tile_bytes(bk, bn, dt)
    vec = _tile_bytes(1, bn, 4)
    o_t = _tile_bytes(bm, bn, dt)
    acc = _tile_bytes(bm, bn, 4)
    return 2 * (x_t + w_t + vec * (2 if quantized else 1) + o_t) + 2 * acc


def gemm_plan_fits(shape: GemmShape, plan: GemmPlan,
                   vmem_budget: int = VMEM_BYTES) -> bool:
    """Pure feasibility predicate for a GEMM blocking (see
    :func:`plan_fits`) — the :func:`enumerate_gemm_plans` pruning
    constraint as a side-effect-free function."""
    return gemm_vmem_bytes(shape, plan.bm, plan.bn, plan.bk) <= vmem_budget


def score_gemm_plan(shape: GemmShape, bm: int, bn: int,
                    bk: int) -> Tuple[float, float]:
    """(t_compute, t_memory) roofline terms PER CALL for one blocking.

    Models the traffic ``matmul_pipe``'s index maps generate: the x tile
    is re-fetched once per N-tile, the w tile once per M-tile, the output
    written once; padded lanes (block-rounded M/N/K) are charged as both
    traffic and compute, the GEMM analogue of Fig. 7's channel-padding
    waste. ``dtype`` shrinks streamed bytes and (int8) doubles the MXU
    rate via :func:`repro.core.roofline.time_bounds`.
    """
    dt = _DTYPE_BYTES.get(shape.dtype, 4)
    bm, bn, bk = min(bm, shape.m), min(bn, shape.n), min(bk, shape.k)
    mp, np_, kp = (_round_up(shape.m, bm), _round_up(shape.n, bn),
                   _round_up(shape.k, bk))
    n_m, n_n = mp // bm, np_ // bn
    x_bytes = n_n * mp * kp * dt
    w_bytes = n_m * kp * np_ * dt
    o_bytes = mp * np_ * dt
    flops = 2 * mp * np_ * kp
    return time_bounds(flops, x_bytes + w_bytes + o_bytes,
                       mxu_util=mxu_utilization(bk, bn),
                       dtype=shape.dtype)


def enumerate_gemm_plans(shape: GemmShape,
                         vmem_budget: int = VMEM_BYTES) -> List[GemmPlan]:
    """All (bm, bn, bk) points that fit the VMEM budget and lower on
    the target: ``bn``/``bk`` are lane dims (multiples of 128 or the
    whole dim), ``bm`` a sublane dim (a multiple of the dtype's sublane
    tile — 8 fp32, 32 int8 — or the whole dim)."""
    sub = _sublanes(_DTYPE_BYTES.get(shape.dtype, 4))
    bm_cands = sorted({min(v, shape.m) for v in
                       _pow2_upto(min(shape.m, 4 * MXU_DIM), lo=sub)})
    bn_cands = sorted({min(v, shape.n)
                       for v in _pow2_upto(min(shape.n, 4 * MXU_DIM),
                                           lo=LANE)})
    bk_cands = sorted({min(v, shape.k)
                       for v in _pow2_upto(min(shape.k, 8 * MXU_DIM),
                                           lo=LANE)})
    plans = []
    for bm in bm_cands:
        for bn in bn_cands:
            for bk in bk_cands:
                vmem = gemm_vmem_bytes(shape, bm, bn, bk)
                if vmem > vmem_budget:
                    continue
                tc, tm = score_gemm_plan(shape, bm, bn, bk)
                plans.append(GemmPlan(bm, bn, bk, vmem_bytes=vmem,
                                      t_model=max(tc, tm)))
    return plans


def best_gemm_plan(shape: GemmShape,
                   vmem_budget: int = VMEM_BYTES) -> GemmPlan:
    """Lowest modelled-time feasible blocking (larger tiles break ties)."""
    plans = enumerate_gemm_plans(shape, vmem_budget)
    if not plans:
        raise ValueError(
            f"no feasible GEMM plan for {shape} under {vmem_budget} B VMEM")
    return min(plans, key=lambda p: (p.t_model, -(p.bm * p.bn * p.bk)))


def measure_gemm_plan(shape: GemmShape, plan: GemmPlan, *, iters: int = 3,
                      warmup: int = 1,
                      interpret: Optional[bool] = None) -> float:
    """Wall-clock seconds/call for one GEMM blocking (the FC side).

    The classifier mirror of :func:`measure_plan`: deterministic split
    operand streams per ``(shape, plan)``, backend-aware ``interpret``
    default, int8 shapes measured through the actual fixed-point kernel
    (quantized operands + requantize scale, exactly what the plan's VMEM
    feasibility was modeled at). Counted as ``gemm_measured`` in
    :func:`measure_stats`.
    """
    import time

    import jax
    import jax.numpy as jnp

    from repro.kernels.matmul_pipe import matmul_pipe

    interpret = resolve_interpret(interpret)
    kx, kw = jax.random.split(jax.random.key(_measure_seed(shape, plan)))
    x = jax.random.normal(kx, (shape.m, shape.k), jnp.float32)
    w = jax.random.normal(kw, (shape.k, shape.n), jnp.float32) * 0.1
    b = jnp.zeros((shape.n,))
    qkw = {}
    if shape.dtype == "int8":
        from repro.quant.core import (abs_max_scale, quantize,
                                      quantize_channelwise)
        sx = float(abs_max_scale(x))
        w, ws = quantize_channelwise(w, axis=-1)
        x = quantize(x, sx)
        qkw = dict(scale=ws * sx, out_scale=0.05)
    else:
        dt = jnp.float32 if shape.dtype == "float32" else jnp.bfloat16
        x, w, b = x.astype(dt), w.astype(dt), b.astype(dt)

    def run():
        return matmul_pipe(x, w, b, bm=plan.bm, bn=plan.bn, bk=plan.bk,
                           interpret=interpret, **qkw)

    for _ in range(max(1, warmup)):           # compile / warm up
        run().block_until_ready()
    t0 = time.perf_counter()   # repro: allow[RPA102] the measurement harness
    for _ in range(max(1, iters)):
        run().block_until_ready()
    _MEASURE_STATS["gemm_measured"] += 1
    # repro: allow[RPA102] measured seconds/call IS this function's output
    return (time.perf_counter() - t0) / max(1, iters)


_GEMM_REGISTRY: Dict[Tuple[GemmShape, str, int], GemmPlan] = {}

# DSE accounting: how many sweeps actually ran vs how many lookups the
# registry absorbed. This is the compile-phase "instruction count" —
# deterministic (unlike wall time), so benchmarks/run.py can gate on it
# and tests can assert that a loaded plan table skips the sweep entirely.
_SWEEP_STATS = {"conv_sweeps": 0, "conv_hits": 0,
                "gemm_sweeps": 0, "gemm_hits": 0}


def sweep_stats() -> Dict[str, int]:
    """A snapshot of the DSE sweep/cache-hit counters."""
    return dict(_SWEEP_STATS)


def reset_sweep_stats() -> None:
    for k in _SWEEP_STATS:
        _SWEEP_STATS[k] = 0


# Measurement accounting, mirroring the sweep counters above: how many
# wall-clock kernel measurements actually ran (``*_measured``) vs how
# many the profiler's cache absorbed (``*_measure_hits``). The counts
# are deterministic even though the times are not, so tests and
# benchmarks/run.py can assert that a compile seeded from a measured
# plan table runs ZERO measurements.
_MEASURE_STATS = {"conv_measured": 0, "conv_measure_hits": 0,
                  "gemm_measured": 0, "gemm_measure_hits": 0}


def measure_stats() -> Dict[str, int]:
    """A snapshot of the kernel-measurement/cache-hit counters."""
    return dict(_MEASURE_STATS)


def reset_measure_stats() -> None:
    for k in _MEASURE_STATS:
        _MEASURE_STATS[k] = 0


def count_measure_hit(kind: str) -> None:
    """Record a profiler measurement-cache hit (``kind`` conv|gemm)."""
    _MEASURE_STATS[f"{kind}_measure_hits"] += 1


# Active lookup recorders: every get_plan / get_gemm_plan resolution
# (hit OR sweep) is appended to each open recorder in snapshot format.
# ``repro.pipeline.compile_cnn`` opens one around the whole compile, so
# the resulting plan table contains EVERY key the compiled pipeline will
# ever look up — including the stage planner's microbatch sweep — and a
# table loaded into a fresh process satisfies all of them without one
# sweep.
_RECORDERS: List[Dict[str, list]] = []


@contextlib.contextmanager
def record_lookups() -> Iterator[Dict[str, list]]:
    """Record every plan lookup inside the block.

    Yields a dict ``{"conv": [rows...], "gemm": [rows...]}`` in the same
    record format as :func:`registry_snapshot` (duplicates included;
    callers dedupe).
    """
    rows: Dict[str, list] = {"conv": [], "gemm": []}
    _RECORDERS.append(rows)
    try:
        yield rows
    finally:
        # remove by IDENTITY: nested recorders hold equal dict contents
        # (every row goes to all open recorders), so list.remove's
        # equality match would drop the wrong one
        _RECORDERS[:] = [r for r in _RECORDERS if r is not rows]


def _record(kind: str, shape, backend: str, vmem_budget: int, plan) -> None:
    if _RECORDERS:
        row = {"shape": shape.to_dict(), "backend": backend,
               "vmem_budget": vmem_budget, "plan": plan.to_dict()}
        for rec in _RECORDERS:
            rec[kind].append(row)


def get_gemm_plan(shape: GemmShape, *, vmem_budget: int = VMEM_BYTES,
                  backend: str = "tpu") -> GemmPlan:
    """Memoised best GEMM plan (dtype rides inside the shape key)."""
    key = (shape, backend, vmem_budget)
    plan = _GEMM_REGISTRY.get(key)
    if plan is None:
        _SWEEP_STATS["gemm_sweeps"] += 1
        plan = best_gemm_plan(shape, vmem_budget)
        _GEMM_REGISTRY[key] = plan
    else:
        _SWEEP_STATS["gemm_hits"] += 1
    _record("gemm", shape, backend, vmem_budget, plan)
    return plan


def gemm_plan_for_layer(m: int, k: int, n: int, *, dtype: str = "float32",
                        vmem_budget: int = VMEM_BYTES,
                        backend: str = "tpu") -> GemmPlan:
    """Convenience: tune one FC layer — ``m`` rows (the serving
    micro-batch), ``k`` -> ``n`` features. The batch is part of the key,
    so serving at a new micro-batch retunes the classifier."""
    return get_gemm_plan(GemmShape(m=m, k=k, n=n, dtype=dtype),
                         vmem_budget=vmem_budget, backend=backend)


def gemm_registry_snapshot() -> List[dict]:
    """JSON-serialisable view of every tuned GEMM (for BENCH_conv.json)."""
    return [{"shape": k[0].to_dict(), "backend": k[1],
             "vmem_budget": k[2], "plan": p.to_dict()} for k, p in sorted(
                 _GEMM_REGISTRY.items(), key=lambda kv: repr(kv[0]))]


# ---------------------------------------------------------------------------
# plan registry: (layer shape, dtype, backend) -> ConvPlan
# ---------------------------------------------------------------------------

_REGISTRY: Dict[Tuple[ConvShape, str, int], ConvPlan] = {}


def get_plan(shape: ConvShape, *, vmem_budget: int = VMEM_BYTES,
             backend: str = "tpu") -> ConvPlan:
    """Memoised best plan for a layer shape (dtype rides inside shape).

    The budget is part of the key: a plan tuned for a tight budget must
    not be handed to a caller with the full 16 MiB (or vice versa)."""
    key = (shape, backend, vmem_budget)
    plan = _REGISTRY.get(key)
    if plan is None:
        _SWEEP_STATS["conv_sweeps"] += 1
        plan = best_plan(shape, vmem_budget)
        _REGISTRY[key] = plan
    else:
        _SWEEP_STATS["conv_hits"] += 1
    _record("conv", shape, backend, vmem_budget, plan)
    return plan


def plan_for_layer(x_shape: Tuple[int, ...], w_shape: Tuple[int, ...], *,
                   stride: int = 1, pad: int = 0, groups: int = 1,
                   pool: Optional[str] = None, pool_k: int = 2,
                   pool_s: int = 2, dtype: str = "float32",
                   vmem_budget: int = VMEM_BYTES,
                   backend: str = "tpu") -> ConvPlan:
    """Convenience: build the ConvShape key from array shapes and tune.

    The batch in ``x_shape`` becomes part of the key, so serving at a new
    micro-batch retunes (and re-caches) the layer for that batch.
    """
    b, h, w, c = x_shape
    kh, kw, _, m = w_shape
    shape = ConvShape(h=h, w=w, c=c, kh=kh, kw=kw, m=m, stride=stride,
                      pad=pad, groups=groups, pool=pool, pool_k=pool_k,
                      pool_s=pool_s, dtype=dtype, b=b)
    return get_plan(shape, vmem_budget=vmem_budget, backend=backend)


def clear_registry() -> None:
    _REGISTRY.clear()
    _GEMM_REGISTRY.clear()


def registry_snapshot() -> List[dict]:
    """JSON-serialisable view of every tuned layer (for BENCH_conv.json)."""
    return [{"shape": k[0].to_dict(), "backend": k[1],
             "vmem_budget": k[2], "plan": p.to_dict()} for k, p in sorted(
                 _REGISTRY.items(), key=lambda kv: repr(kv[0]))]


def dump_registry(path: str) -> None:
    """Deprecated: write the unified registry export instead.

    Historically this wrote a bare JSON list of conv records, a third
    export shape next to the plan table. There is now ONE shape — the
    provenance-carrying ``PlanTable`` document — so this shim writes
    ``repro.pipeline.PlanTable.from_registry().save(path)`` (conv + gemm
    + sweep-stat provenance) and warns. Imported lazily to keep
    ``repro.kernels`` free of a pipeline dependency.
    """
    warnings.warn(
        "autotune.dump_registry is deprecated; use "
        "repro.pipeline.PlanTable.from_registry().save(path) — the "
        "output is now the PlanTable format, not a bare list",
        DeprecationWarning, stacklevel=2)
    from repro.pipeline.plan_table import PlanTable
    PlanTable.from_registry().save(path)


def seed_registry(conv_rows: List[dict] = (),
                  gemm_rows: List[dict] = ()) -> int:
    """Insert serialised plan records back into the process registries.

    The inverse of :func:`registry_snapshot` / :func:`gemm_registry_snapshot`
    — ``repro.pipeline`` uses it to make a committed plan table (the
    JSON a previous compile saved) satisfy every ``get_plan`` /
    ``get_gemm_plan`` lookup without running a sweep. Records whose key
    is already present are left alone (the registry stays authoritative);
    returns the number of records inserted. Shape dicts missing newer
    fields deserialise with the dataclass defaults, exactly like old
    BENCH_conv.json records.
    """
    inserted = 0
    for row in conv_rows:
        key = (ConvShape(**row["shape"]), row["backend"],
               row["vmem_budget"])
        if key not in _REGISTRY:
            _REGISTRY[key] = ConvPlan(**row["plan"])
            inserted += 1
    for row in gemm_rows:
        gkey = (GemmShape(**row["shape"]), row["backend"],
                row["vmem_budget"])
        if gkey not in _GEMM_REGISTRY:
            _GEMM_REGISTRY[gkey] = GemmPlan(**row["plan"])
            inserted += 1
    return inserted
