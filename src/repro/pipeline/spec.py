"""ExecutionSpec: the compile-time contract of the PipeCNN pipeline.

PipeCNN configures its kernel cascade ONCE — channel depths, VEC_SIZE /
CU_NUM, fixed-point mode — and then only enqueues work. This module is
that configuration step as a typed object: the 10+ orthogonal knobs that
accreted onto ``CNNConfig`` across PRs 1–4 split into four sub-specs
whose legal combinations are validated at CONSTRUCTION time, not five
frames deep inside pallas tracing.

  * :class:`Precision` — compute dtype and the fixed-point mode (the
    paper's fp32 vs fixed-point resource trade);
  * :class:`Tiling`   — the DSE knobs (VEC_SIZE/CU_NUM analogues, VMEM
    budget, line-buffer depth, batch fold);
  * :class:`Placement` — the fleet shape (data-parallel replicas x
    pipeline stages over the 2-D device mesh);
  * :class:`Serving`  — the request-loop knobs (micro-batch, admission
    bound, clock).

``ExecutionSpec`` composes them plus the backend selection
(``use_pallas``, ``interpret``); :func:`resolve_config` folds a spec
back onto a :class:`~repro.core.config.CNNConfig` (the runtime carrier
every kernel-level function consumes), and :func:`spec_from_config`
lifts a legacy CNNConfig into a spec — the bridge the deprecation shims
ride.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Optional

from repro.core.config import CNNConfig, SpecError
from repro.serve.scheduler import AutoscalePolicy


@dataclass(frozen=True)
class Precision:
    """What numbers flow through the pipeline.

    ``quant="int8"`` selects the paper's fixed-point mode: calibrated
    symmetric int8 activations/weights, int32 MXU accumulation, fused
    requantize epilogues. ``calib`` is the synthetic calibration-batch
    size used when ``compile_cnn`` is not handed a calibration batch or
    pre-quantized params.
    """
    dtype: str = "float32"             # fp compute dtype: float32|bfloat16
    quant: str = "none"                # "none" | "int8"
    calib: int = 8                     # calibration images (quant="int8")


@dataclass(frozen=True)
class Tiling:
    """The kernel design space (the paper's Fig. 7 sweep axes)."""
    autotune: bool = True              # per-layer (b,c,m,oh)_blk DSE
    vmem_budget: int = 16 * 2 ** 20    # the TPU's "DSP count"
    vec_size: int = 8                  # manual c_blk fallback (VEC_SIZE)
    cu_num: int = 16                   # manual m_blk fallback (CU_NUM)
    oh_blk: int = 0                    # manual line-buffer depth (0=full)
    b_blk: int = 1                     # manual images per grid step


@dataclass(frozen=True)
class Placement:
    """Where the pipeline runs: the (data, pipe) mesh shape."""
    replicas: int = 1                  # mesh "data" axis (DP replicas)
    pp_stages: int = 1                 # mesh "pipe" axis (GPipe stages)
    microbatches: int = 0              # GPipe M per round (0 = auto-sweep)


@dataclass(frozen=True)
class Serving:
    """The request loop around the compiled forward.

    ``retries``/``backoff`` are the resilience contract under injected
    replica faults (see ``repro.serve.faults``): a request lost to a
    failure re-dispatches up to ``retries`` times, waiting
    ``backoff * 2**(attempt-1)`` seconds before re-admission; past the
    budget it ends as an explicit ``Completion(status="failed")``.
    ``slo`` is a per-request latency bound the report counts violations
    of (0 = no SLO).

    ``scheduler`` selects the unit of scheduling: ``"gang"`` (padded
    super-batch rounds, the default) or ``"continuous"`` (per-request
    slots admitted/retired at microbatch boundaries — requires the
    modeled clock, see ``repro.serve.scheduler``). ``steal_threshold``
    and ``autoscale`` only exist under the continuous scheduler: queue
    skew deeper than the threshold triggers work stealing (each steal
    charges the request's retry budget, so it needs ``retries >= 1``),
    and an :class:`~repro.serve.scheduler.AutoscalePolicy` lets the
    fleet elastically scale between its min/max replicas.
    """
    batch: int = 8                     # micro-batch queues pad requests to
    max_queue: int = 0                 # admission bound (0 = unbounded)
    clock: str = "measured"            # "measured" | "modeled"
    execute: bool = True               # False = device-free simulation
    retries: int = 0                   # re-dispatch budget per request
    backoff: float = 0.0               # base re-admission delay (seconds)
    slo: float = 0.0                   # latency bound (seconds, 0 = off)
    scheduler: str = "gang"            # "gang" | "continuous"
    steal_threshold: int = 0           # queue-skew steal trigger (0 = off)
    autoscale: Optional[AutoscalePolicy] = None   # elastic fleet policy


@dataclass(frozen=True)
class ExecutionSpec:
    """One immutable description of a compiled pipeline.

    ``__post_init__`` cross-validates the sub-specs against each other:
    every contradiction listed below used to surface as a shape error
    inside pallas tracing or a silently wrong serving run.
    """
    precision: Precision = field(default_factory=Precision)
    tiling: Tiling = field(default_factory=Tiling)
    placement: Placement = field(default_factory=Placement)
    serving: Serving = field(default_factory=Serving)
    use_pallas: bool = True            # fused kernels vs the XLA reference
    # None = follow the backend (compiled on a TPU, interpreted
    # elsewhere); True/False pins it for everything this compile runs.
    # compile_cnn refuses True on a TPU: it would hide the device
    interpret: Optional[bool] = None

    def __post_init__(self):
        p, t, pl, s = self.precision, self.tiling, self.placement, \
            self.serving
        if p.dtype not in ("float32", "bfloat16"):
            raise SpecError(
                "Precision.dtype",
                f"Precision.dtype={p.dtype!r}: float32 or bfloat16")
        if p.quant not in ("none", "int8"):
            raise SpecError(
                "Precision.quant",
                f"Precision.quant={p.quant!r}: none or int8")
        if p.quant == "int8" and p.dtype != "float32":
            raise SpecError(
                "Precision.quant",
                "Precision.quant='int8' with dtype='bfloat16' is "
                "contradictory: the fixed-point pipeline carries int8 "
                "codes with int32 accumulation; its fp boundary (logits, "
                "LRN detour, calibration) is float32 by construction")
        if p.quant == "int8" and p.calib <= 0:
            raise SpecError(
                "Precision.calib",
                "Precision.quant='int8' needs a calibration source: set "
                "Precision.calib > 0 or hand compile_cnn a calibration "
                "batch / a QuantizedCNNParams")
        if t.vmem_budget <= 0:
            raise SpecError(
                "Tiling.vmem_budget",
                f"Tiling.vmem_budget={t.vmem_budget}: must "
                "be a positive byte budget")
        if s.batch < 1:
            raise SpecError(
                "Serving.batch",
                f"Serving.batch={s.batch}: must be >= 1")
        if s.max_queue < 0:
            raise SpecError(
                "Serving.max_queue",
                f"Serving.max_queue={s.max_queue}: 0 "
                "(unbounded) or a positive bound")
        if s.clock not in ("measured", "modeled"):
            raise SpecError(
                "Serving.clock",
                f"Serving.clock={s.clock!r}: measured or modeled")
        if not s.execute and s.clock == "measured":
            raise SpecError(
                "Serving.execute",
                "Serving.execute=False with clock='measured' is "
                "contradictory: a device-free simulation has no wall "
                "time to measure — use clock='modeled'")
        if s.retries < 0:
            raise SpecError(
                "Serving.retries",
                f"Serving.retries={s.retries}: must be >= 0")
        if s.backoff < 0 or s.slo < 0:
            raise SpecError(
                "Serving.backoff",
                f"Serving.backoff={s.backoff} / slo={s.slo}: both are "
                "seconds >= 0")
        if s.backoff and not s.retries:
            raise SpecError(
                "Serving.backoff",
                "Serving.backoff set with retries=0 is contradictory: "
                "backoff only delays re-admission of retried requests")
        if s.scheduler not in ("gang", "continuous"):
            raise SpecError(
                "Serving.scheduler",
                f"Serving.scheduler={s.scheduler!r}: gang "
                "or continuous")
        if s.scheduler == "continuous" and s.clock != "modeled":
            raise SpecError(
                "Serving.scheduler",
                "Serving.scheduler='continuous' requires "
                "clock='modeled': slot service and microbatch-boundary "
                "times come from the roofline model, not wall time")
        if s.steal_threshold < 0:
            raise SpecError(
                "Serving.steal_threshold",
                f"Serving.steal_threshold={s.steal_threshold}: 0 "
                "(stealing off) or a positive queue-skew depth")
        if (s.steal_threshold or s.autoscale is not None) and \
                s.scheduler != "continuous":
            raise SpecError(
                "Serving.steal_threshold",
                "Serving.steal_threshold / autoscale only exist under "
                "scheduler='continuous': gang rounds have no "
                "per-request slots to steal or scale")
        if s.autoscale is not None and not (
                s.autoscale.min_replicas <= pl.replicas
                <= s.autoscale.max_replicas):
            raise SpecError(
                "Placement.replicas",
                f"Placement.replicas={pl.replicas} outside the "
                f"autoscale range [{s.autoscale.min_replicas}, "
                f"{s.autoscale.max_replicas}]")
        if t.b_blk > 1 and s.batch % t.b_blk:
            raise SpecError(
                "Tiling.b_blk",
                f"Serving.batch={s.batch} is not a multiple of "
                f"Tiling.b_blk={t.b_blk}: the queue pads requests to the "
                f"serving batch, so the conv grid's image block must "
                f"divide it")
        if pl.replicas < 1 or pl.pp_stages < 1:
            raise SpecError(
                "Placement.replicas",
                f"Placement.replicas={pl.replicas} / "
                f"pp_stages={pl.pp_stages}: both must be >= 1")
        if pl.microbatches:
            if pl.pp_stages == 1:
                raise SpecError(
                    "Placement.microbatches",
                    "Placement.microbatches set without pipeline stages "
                    "(pp_stages=1): GPipe microbatching only exists on "
                    "the 'pipe' mesh axis")
            if s.batch % pl.microbatches:
                raise SpecError(
                    "Placement.microbatches",
                    f"Placement.microbatches={pl.microbatches} must "
                    f"divide Serving.batch={s.batch} so every microbatch "
                    f"compiles once")

    @property
    def run_dtype(self) -> str:
        """The dtype plans/costs are keyed by ('int8' when quantized)."""
        return "int8" if self.precision.quant == "int8" else \
            self.precision.dtype

    @property
    def mode(self) -> str:
        R, S = self.placement.replicas, self.placement.pp_stages
        return ("single" if R * S == 1 else "dp" if S == 1 else
                "pp" if R == 1 else "hybrid")


def spec_from_config(cfg: CNNConfig, **overrides) -> ExecutionSpec:
    """Lift a legacy knob-sprawl CNNConfig into an ExecutionSpec.

    The inverse of :func:`resolve_config`; the deprecation shims
    (``models.cnn.cnn_forward``, ``launch.serve_cnn.serve``) use it to
    route old call sites through the compile-once path unchanged.
    ``overrides`` replace top-level ExecutionSpec fields (e.g.
    ``use_pallas=...``) or whole sub-specs.
    """
    spec = ExecutionSpec(
        precision=Precision(dtype=cfg.dtype, quant=cfg.quant,
                            calib=cfg.calib),
        tiling=Tiling(autotune=cfg.autotune, vmem_budget=cfg.vmem_budget,
                      vec_size=cfg.vec_size, cu_num=cfg.cu_num,
                      oh_blk=cfg.oh_blk, b_blk=cfg.b_blk),
        placement=Placement(replicas=cfg.replicas, pp_stages=cfg.pp_stages,
                            microbatches=cfg.serve_microbatches),
        serving=Serving(batch=cfg.serve_batch, max_queue=cfg.max_queue))
    return dataclasses.replace(spec, **overrides) if overrides else spec


def resolve_config(cfg: CNNConfig, spec: ExecutionSpec) -> CNNConfig:
    """Fold a spec onto the architecture config.

    The result is the one CNNConfig every kernel-level consumer
    (``run_group``, the stage planner, the engine) sees — the spec is
    authoritative for every knob it covers, the architecture fields
    (layers, input size, classes) come from ``cfg``. CNNConfig's own
    ``__post_init__`` re-validates the combination against the layer
    stack (e.g. pp_stages vs the fusion-group count).
    """
    return dataclasses.replace(
        cfg,
        dtype=spec.precision.dtype, quant=spec.precision.quant,
        calib=spec.precision.calib,
        autotune=spec.tiling.autotune, vmem_budget=spec.tiling.vmem_budget,
        vec_size=spec.tiling.vec_size, cu_num=spec.tiling.cu_num,
        oh_blk=spec.tiling.oh_blk, b_blk=spec.tiling.b_blk,
        replicas=spec.placement.replicas,
        pp_stages=spec.placement.pp_stages,
        serve_microbatches=spec.placement.microbatches,
        serve_batch=spec.serving.batch, max_queue=spec.serving.max_queue)
