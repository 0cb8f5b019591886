"""compile_cnn: the offline compile phase of the PipeCNN toolflow.

The FPGA toolflow pattern (FFCNN 2022; the survey literature's
accelerator-compiler split): an explicit *compile* phase that resolves
every decision — kernel tilings, fixed-point scales, stage partition,
mesh placement — into a fixed execution plan, and a thin *run* phase
that only enqueues work. ``compile_cnn(cfg, spec, params)`` is that
compile step:

  * freezes one tiling plan per conv and fc fusion group at the declared
    serving batch and dtype (the conv + GEMM DSE's, or ``Tiling``'s
    manual knobs with autotune off), and tunes the DP super-batch and
    every GPipe microbatch candidate too: ``models.cnn.run_group`` only
    reads these plans, so no lookup is left for runtime;
  * runs int8 calibration when ``spec.precision.quant == "int8"`` and
    the params are not already quantized;
  * runs the stage planner and constructs the ``(data, pipe)`` device
    mesh for dp/pp/hybrid placements (via :class:`repro.serve.ServeEngine`);
  * freezes everything into an immutable :class:`CompiledCNN` whose
    plan table serialises to JSON (``save_plan``/``load_plan``) — a
    committed artifact seeds the autotune registries and a re-compile
    performs ZERO sweeps (``autotune.sweep_stats`` proves it).

``CompiledCNN`` then exposes the whole runtime surface: ``.forward``,
``.forward_stage``, ``.serve``, ``.plans``.
"""
from __future__ import annotations

import contextlib
import itertools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.config import CNNConfig, SpecError
from repro.kernels import autotune, ops
from repro.kernels.conv_pipe import s2d_geometry, subsamples
from repro.kernels.mode import backend_interprets
from repro.obs.trace import SPANS, now_ns
from repro.pipeline.plan_table import PlanTable, load_plan, plan_key
from repro.pipeline.spec import ExecutionSpec, resolve_config, \
    spec_from_config


def _group_shapes(cfg: CNNConfig, batch: int, dtype: str):
    """Yield ``(group, kind, shape)`` — the tuning key of every fusion
    group at (batch, dtype): a ``ConvShape`` for conv(+pool) groups, a
    ``GemmShape`` for fc groups. One shape constructor shared by the DSE
    resolve and the roofline breakdown, so they can never disagree on
    what a group's plan was tuned for."""
    from repro.serve.stage_planner import conv_shape, group_io_shapes

    for group, in_shape, out_shape in group_io_shapes(cfg):
        l = cfg.layers[group[0]]
        if l.kind == "conv":
            yield group, "conv", conv_shape(cfg, group, in_shape, dtype,
                                            batch)
        elif l.kind == "fc":
            k = 1
            for d in in_shape:
                k *= d
            yield group, "gemm", autotune.GemmShape(
                m=batch, k=k, n=out_shape[-1], dtype=dtype)


def _note_trace(x, placement: str) -> None:
    """Mark (``cnn.retrace`` instant) and count one trace of a jitted
    forward. It runs while ``jax.jit`` traces, so once for each input
    shape, dtype or placement not traced before, the first included; a
    ``lower()`` at a shape not yet traced counts too."""
    SPANS.instant("cnn.retrace", ("shape", tuple(x.shape),
                                  "dtype", str(x.dtype),
                                  "placement", placement))
    SPANS.count("cnn.retrace")


def resolve_group_plans(cfg: CNNConfig, batch: int,
                        dtype: str) -> Dict[Tuple[int, ...], Any]:
    """The plan of every conv and fc fusion group at (batch, dtype) — the
    frozen mapping ``run_group`` executes with. With ``cfg.autotune`` on,
    one DSE lookup per group (registry-memoised: a second compile over
    the same spec is pure cache hits); off, the manual VEC_SIZE/CU_NUM
    knobs, as plans."""
    plans: Dict[Tuple[int, ...], Any] = {}
    for group, kind, shape in _group_shapes(cfg, batch, dtype):
        if kind == "conv":
            plans[group] = (
                autotune.get_plan(shape, vmem_budget=cfg.vmem_budget)
                if cfg.autotune else autotune.ConvPlan(
                    c_blk=cfg.vec_size, m_blk=max(8, cfg.cu_num),
                    oh_blk=cfg.oh_blk, b_blk=cfg.b_blk))
        else:
            # manual batched-FC blocks (paper §IV batch-64 mode): bm
            # covers the micro-batch, so each weight tile fetched from
            # HBM is applied to every image before the next streams in
            plans[group] = (
                autotune.get_gemm_plan(shape, vmem_budget=cfg.vmem_budget)
                if cfg.autotune else autotune.GemmPlan(
                    bm=max(128, cfg.serve_batch),
                    bn=128 * max(1, cfg.cu_num // 8),
                    bk=128 * max(1, cfg.vec_size // 8)))
    return plans


def _count_conv_paths(cfg: CNNConfig, batch: int, dtype: str,
                      plans: Dict[Tuple[int, ...], Any]) -> None:
    """Count (``conv.kw_fold``) each conv group whose column taps
    ``conv_pipe`` folds into its MXU contraction, (``conv.kh_fold``) each
    whose row taps fold too (:func:`~repro.kernels.conv_pipe.s2d_geometry`
    decides), (``conv.pool_fused``) each whose pool runs in its epilogue,
    (``conv.residual_fused``) each whose epilogue adds a residual
    operand, (``conv.subsampled``) each strided 1x1 conv run on its
    subsampled input (:func:`~repro.kernels.conv_pipe.subsamples`), and
    (``conv.halo_in_kernel``) each whose halo it makes in VMEM under its
    plan (:func:`~repro.kernels.autotune.halo_in_kernel`)."""
    for group, kind, s in _group_shapes(cfg, batch, dtype):
        if kind != "conv":
            continue
        p = plans[group]
        if autotune.halo_in_kernel(s, p.c_blk, p.m_blk, p.oh_blk, p.b_blk,
                                   cfg.vmem_budget):
            SPANS.count("conv.halo_in_kernel")
        g = s2d_geometry(s.h, s.w, s.c // s.groups, s.kh, s.kw,
                         stride=s.stride, pad=s.pad)
        if g.kw_fold > 1:
            SPANS.count("conv.kw_fold")
        if g.kh_fold > 1:
            SPANS.count("conv.kh_fold")
        if s.pool is not None:
            SPANS.count("conv.pool_fused")
        if s.residual:
            SPANS.count("conv.residual_fused")
        if subsamples(s.kh, s.kw, s.stride):
            SPANS.count("conv.subsampled")


class CompiledCNN:
    """A fully-resolved, immutable-plan CNN pipeline.

    Construct via :func:`compile_cnn` — everything shape-, precision- or
    placement-dependent was decided at compile time; the methods here
    only run. Runtime surface:

      * :meth:`forward` — logits for a batch (single, dp-sharded or
        pipeline-parallel, per the compiled placement);
      * :meth:`forward_stage` — one pipeline stage on a boundary
        activation (the unit the fleet engine streams);
      * :meth:`serve` — the request loop; returns a
        :class:`~repro.serve.report.FleetReport` (completions ride on
        ``report.completions``);
      * :meth:`plans` / :meth:`save_plan` / :meth:`load_plan` — the
        frozen DSE results as data.
    """

    def __init__(self, *, cfg: CNNConfig, spec: ExecutionSpec, params,
                 quant: bool, group_plans: Dict[Tuple[int, ...], Any],
                 plan_table: PlanTable, engine=None):
        self.cfg = cfg                     # the RESOLVED CNNConfig
        self.spec = spec
        self.params = params
        self.quant = quant
        self.group_plans = dict(group_plans)
        self.plan_table = plan_table
        self.engine = engine
        from repro.models.cnn import fuse_plan
        self._fuse = fuse_plan(cfg)
        self._fwd = None                   # lazily-jitted single forward
        self._pp_fwd = None                # lazily-jitted pipeline forward
        self._batches = itertools.count()  # forward's batch ids

    # -- plumbing ----------------------------------------------------------

    def _ctx(self):
        """Thread the spec's interpret choice through every run."""
        if self.spec.interpret is None:
            return contextlib.nullcontext()
        return ops.interpret_mode(self.spec.interpret)

    @property
    def mode(self) -> str:
        return self.spec.mode

    @property
    def mesh(self):
        return self.engine.mesh if self.engine is not None else None

    @property
    def stage_plan(self):
        return self.engine.stage_plan if self.engine is not None else None

    @property
    def stages(self) -> Tuple[Tuple[Tuple[int, ...], ...], ...]:
        """Per-stage fusion groups: the compiled stage partition, or one
        stage per fusion group when no pipeline placement was compiled."""
        sp = self.stage_plan
        if sp is not None:
            return tuple(s.groups for s in sp.stages)
        return tuple((g,) for g in self._fuse)

    @property
    def n_stages(self) -> int:
        return len(self.stages)

    def _single_forward(self):
        """The jitted whole-network fold over the frozen plan table."""
        if self._fwd is None:
            from repro.models.cnn import cnn_forward_stage
            cfg, groups, plans = self.cfg, self._fuse, self.group_plans
            up, mode = self.spec.use_pallas, self.mode

            def fold(p, x):
                return cnn_forward_stage(p, x, cfg, groups, plans=plans,
                                         use_pallas=up)

            if self.spec.placement.replicas > 1 and self.mesh is not None:
                from repro.parallel.sharding import data_parallel
                fold = data_parallel(fold, self.mesh)

            def f(p, x):
                _note_trace(x, mode)
                return fold(p, x)

            self._fwd = jax.jit(f)
        return self._fwd

    def _pipeline_forward(self):
        """The jitted GPipe forward over the compiled stage plan (params
        are arguments, so they are not baked in as constants)."""
        if self._pp_fwd is None:
            from repro.serve.engine import pipeline_logits
            cfg, mesh, sp = self.cfg, self.mesh, self.stage_plan
            n_micro, up = self.engine.n_micro, self.spec.use_pallas
            plans, mode = self.engine.micro_plans, self.mode

            def f(p, x):
                _note_trace(x, mode)
                return pipeline_logits(p, x, cfg, mesh, sp,
                                       n_microbatches=n_micro,
                                       plans=plans, use_pallas=up,
                                       dp_axis="data")

            self._pp_fwd = jax.jit(f)
        return self._pp_fwd

    # -- the run phase -----------------------------------------------------

    def forward(self, x: jax.Array) -> jax.Array:
        """x (B, H, W, C) fp32 -> logits (B, n_classes).

        Runs the compiled placement: plain fold (single), input sharded
        over the mesh "data" axis (dp), or microbatches streamed through
        device-resident stages (pp/hybrid; B must divide into the
        compiled microbatch grid). Numerics are placement-independent:
        fp32 allclose / int8 bit-exact vs the unsharded fold.

        With :data:`repro.obs.SPANS` on (the default) a call records a
        ``cnn.forward`` span (args ``batch``, this object's count of
        calls; ``images``; ``bytes``) over two children: ``cnn.h2d``,
        the host's time in the ``jax.device_put`` of ``x`` to the
        compiled input placement (the call, not the copy's completion
        on the device), and ``cnn.dispatch``, the jitted call up to its
        return of logits that may not be ready yet. Recording waits for
        nothing on the device.
        """
        if not SPANS.enabled:
            with self._ctx():
                fwd, where = self._entry(x)
                return fwd(self.params, jax.device_put(x, where))
        t0 = now_ns()
        with self._ctx():
            fwd, where = self._entry(x)
            t1 = now_ns()
            x_dev = jax.device_put(x, where)
            t2 = now_ns()
            out = fwd(self.params, x_dev)
            t3 = now_ns()
        self._record_forward(x, where, t0, t1, t2, t3)
        return out

    def _entry(self, x):
        """The jitted forward of the compiled placement, and where its
        input goes: the batch sharding over the mesh under dp, else
        None (the default device, where the jitted call would put it)."""
        if self.spec.placement.pp_stages > 1:
            return self._pipeline_forward(), None
        if self.spec.placement.replicas > 1 and self.mesh is not None:
            from repro.parallel.sharding import batch_sharding
            return self._single_forward(), batch_sharding(self.mesh,
                                                          x.shape)
        return self._single_forward(), None

    def _record_forward(self, x, where, t0: int, t1: int, t2: int,
                        t3: int) -> None:
        """One forward's spans from its four clock readings: entry,
        copy call, jitted call, return."""
        nbytes = x.nbytes
        top = SPANS.record("cnn.forward", t0, t3, 0,
                           ("batch", next(self._batches),
                            "images", x.shape[0], "bytes", nbytes))
        SPANS.record("cnn.h2d", t1, t2, top,
                     ("bytes", nbytes, "devices", 1 if where is None
                      else self.mesh.devices.size))
        SPANS.record("cnn.dispatch", t2, t3, top)

    def lower(self, x: jax.Array):
        """The program :meth:`forward` runs, lowered for ``x`` (a
        ``jax.stages.Lowered``): its text names every kernel — on a TPU
        each Pallas kernel is a ``tpu_custom_call`` — and ``.compile()``
        gives the executable. Params are arguments, not constants."""
        with self._ctx():
            if self.spec.placement.pp_stages > 1:
                return self._pipeline_forward().lower(self.params, x)
            return self._single_forward().lower(self.params, x)

    def forward_stage(self, i: int, h: jax.Array) -> jax.Array:
        """Run compiled stage ``i`` on its boundary activation ``h``.

        ``h`` is the previous stage's output — int8 codes at interior
        boundaries of a quantized pipeline (the raw fp32 batch for stage
        0, which quantizes at the network edge), fp32 otherwise.
        """
        from repro.models.cnn import cnn_forward_stage
        with self._ctx():
            return cnn_forward_stage(self.params, h, self.cfg,
                                     self.stages[i],
                                     plans=self.group_plans,
                                     use_pallas=self.spec.use_pallas)

    def serve(self, requests: List, *, faults=None, trace=None,
              metrics=None):
        """Drain a request stream through the compiled fleet.

        Returns the :class:`~repro.serve.report.FleetReport`; the
        per-request :class:`~repro.serve.router.Completion` list rides on
        ``report.completions``. ``faults`` (a
        :class:`~repro.serve.faults.FaultSchedule`) injects replica
        fail/recover chaos into the run — requests lost to a failure
        retry per ``spec.serving.retries``/``backoff``.

        ``trace`` (a :class:`repro.obs.TraceRecorder`) and ``metrics``
        (a :class:`repro.obs.MetricsRegistry`) export the run's event
        timeline and metric streams; the trace additionally carries this
        compile's plan provenance and modeled roofline breakdown in its
        ``otherData``, so every span says which plans it executed.
        """
        if self.engine is None:
            from repro.serve.engine import ServeEngine
            self.engine = ServeEngine.from_spec(self.cfg, self.params,
                                                self.spec,
                                                plans=self.group_plans)
        if trace is not None:
            trace.set_meta("compiled", repr(self))
            trace.set_meta("plan_provenance", self.plan_table.provenance)
            trace.set_meta("roofline_breakdown", self.roofline_breakdown())
        with self._ctx():
            done, rep = self.engine.serve(requests, faults=faults,
                                          trace=trace, metrics=metrics)
        rep.completions = done
        return rep

    def roofline_breakdown(self) -> List[dict]:
        """Per-fusion-group modeled time split at the compiled serving
        batch: where the roofline model says a request's time goes.

        One dict per group — the group's layer indices, kind, its chosen
        plan (as ``to_dict``), the compute/memory roofline terms in
        seconds (conv terms scaled to the batch; GEMM terms are already
        per call at the batch), their max ``t_model``, and which side
        binds. When the plan table carries measurements (format 3, from
        ``compile_cnn(measure=True)`` or an inherited measured
        artifact), each row also reports ``t_measured`` (wall-clock
        seconds/call) and ``drift`` (= measured / modeled, same per-call
        unit) — ``None`` on unmeasured rows, so the modeled view is
        unchanged when no profiler ran.
        """

        batch = self.spec.serving.batch
        dtype = "int8" if self.quant else self.spec.run_dtype
        measured = self.plan_table.measurements()
        rows: List[dict] = []
        for group, kind, shape in _group_shapes(self.cfg, batch, dtype):
            plan = self.group_plans.get(group)
            if kind == "conv":
                if plan is None:        # registry-memoised either way
                    plan = autotune.get_plan(
                        shape, vmem_budget=self.cfg.vmem_budget)
                tc, tm = autotune.score_plan(
                    shape, plan.c_blk, plan.m_blk, plan.oh_blk,
                    plan.b_blk)
                tc, tm = tc * batch, tm * batch   # per-image -> batch
            else:
                if plan is None:
                    plan = autotune.get_gemm_plan(
                        shape, vmem_budget=self.cfg.vmem_budget)
                tc, tm = autotune.score_gemm_plan(
                    shape, plan.bm, plan.bn, plan.bk)
            t_model = max(tc, tm)
            m = measured.get(plan_key(
                {"shape": shape.to_dict(), "backend": "tpu",
                 "vmem_budget": self.cfg.vmem_budget,
                 "plan": plan.to_dict()}))
            rows.append({"group": list(group), "kind": kind,
                         "plan": plan.to_dict(),
                         "t_compute": tc, "t_memory": tm,
                         "t_model": t_model,
                         "bound": "compute" if tc >= tm else "memory",
                         "t_measured": m["t_measured"] if m else None,
                         "drift": (m["t_measured"] / t_model
                                   if m and t_model > 0 else None)})
        return rows

    # -- the frozen plans as data ------------------------------------------

    def plans(self) -> PlanTable:
        """Every DSE decision this compile resolved, as serialisable data."""
        return self.plan_table

    def verify(self, *, strict: bool = False) -> list:
        """Statically re-prove this compile's invariants (VMEM budgets,
        block/halo geometry, spec consistency, fusion-group coverage,
        measured-record joins) via ``repro.analysis`` — no kernel runs,
        no DSE sweep. Returns the findings; ``strict=True`` raises
        :class:`~repro.core.config.SpecError` on the first batch instead
        (the ``serve_cnn --verify`` pre-flight contract)."""
        from repro.analysis.plans import verify_compiled

        findings = verify_compiled(self)
        if strict and findings:
            from repro.core.config import SpecError
            raise SpecError(
                "plan_table",
                f"{len(findings)} static-verification finding(s) for "
                f"{self.cfg.name!r}: "
                + "; ".join(str(f) for f in findings))
        return findings

    def save_plan(self, path: str) -> str:
        """Write the plan table as canonical JSON (byte-stable across
        save/load round trips) — commit it next to ``BENCH_conv.json``
        and a future ``compile_cnn(..., plan_path=...)`` skips the DSE
        sweep entirely."""
        return self.plan_table.save(path)

    load_plan = staticmethod(load_plan)

    def save(self, path: str):
        """Snapshot this compiled pipeline as ONE committed artifact —
        params + plan table + spec under the checkpoint subsystem's
        crash-safety protocol (``_COMMITTED`` marker, atomic rename).
        See ``repro.pipeline.artifact`` for the layout. Returns the
        artifact directory path.
        """
        from repro.pipeline.artifact import save_artifact
        return save_artifact(path, cfg=self.cfg, spec=self.spec,
                             params=self.params, plan_table=self.plan_table)

    @classmethod
    def load(cls, path: str, *, with_engine: bool = True) -> "CompiledCNN":
        """Rebuild a :class:`CompiledCNN` from a committed artifact.

        The saved plan table pre-seeds the autotune registries, so a
        warm load performs zero DSE sweeps — this is the restore path a
        recovering replica (and ``ServeEngine.hot_swap``) pays the
        modeled artifact-restore latency for.
        """
        from repro.pipeline.artifact import load_artifact
        return load_artifact(path, with_engine=with_engine)

    def __repr__(self) -> str:
        return (f"CompiledCNN({self.cfg.name}, mode={self.mode}, "
                f"dtype={self.spec.run_dtype}, "
                f"batch={self.spec.serving.batch}, "
                f"stages={self.n_stages}, "
                f"plans={self.plan_table.summary()})")


def compile_cnn(cfg: CNNConfig, spec: Optional[ExecutionSpec] = None,
                params_or_calib=None, *,
                plans: Optional[PlanTable] = None,
                plan_path: Optional[str] = None,
                key=None, with_engine: bool = True,
                measure: bool = False, measure_opts=None,
                trace=None) -> CompiledCNN:
    """Compile a CNN into a :class:`CompiledCNN` (the toolflow's offline
    phase: precision + plans + placement resolved once, run many).

    ``params_or_calib`` accepts the whole precision lifecycle:

      * ``None`` — fresh ``init_cnn_params`` (deterministic from ``key``);
      * a param list — use as-is (calibrated here when quantizing);
      * a ``QuantizedCNNParams`` — pre-calibrated fixed-point params;
      * an fp32 array — a calibration batch: params are initialised and
        calibrated on it (requires ``spec.precision.quant='int8'``);
      * ``(params, calib_batch)`` — explicit pair for quantization.

    ``plans`` / ``plan_path`` pre-seed the autotune registries from a
    saved plan table so compilation performs no DSE sweep; the returned
    object's own table is re-captured (and is identical for the same
    spec — the registry is authoritative either way). A seeded compile
    also inherits the seed table's measurements AND provenance verbatim
    — it runs zero measurements even with ``measure=True``
    (``autotune.measure_stats`` proves it), preserving artifact
    save→load→save byte-equality.

    ``measure=True`` (cold compiles only) runs the
    ``repro.obs.profiler`` measured-refinement pass over every resolved
    plan: the returned table is format 3, carrying per-plan
    ``t_measured`` + the backend fingerprint, under the protocol in
    ``measure_opts`` (a :class:`~repro.obs.profiler.MeasureOptions`;
    defaults are CI-safe).

    ``trace`` (a :class:`~repro.obs.TraceRecorder`) records the compile
    phase onto the ``compile`` track: one ``sweep`` span over the DSE
    resolve, one ``measure`` span per profiled plan — the compile-side
    half of the serving timeline.

    ``with_engine=False`` skips serving-engine/mesh construction (for a
    single-device forward, which needs none); the engine is then built
    lazily on first ``.serve``.
    """
    import time as _time

    from repro.models.cnn import init_cnn_params
    from repro.quant.calibrate import QuantizedCNNParams, calibrate_cnn

    spec = spec if spec is not None else spec_from_config(cfg)
    if spec.interpret and not backend_interprets():
        raise SpecError(
            "ExecutionSpec.interpret",
            "interpret=True on a TPU backend would run the kernels in the "
            "Pallas interpreter on the host and hide the device; leave "
            "interpret=None to compile them")
    rcfg = resolve_config(cfg, spec)
    quantize = spec.precision.quant == "int8"

    # -- unpack the params/calibration source ------------------------------
    params, calib = params_or_calib, None
    if isinstance(params_or_calib, tuple):
        params, calib = params_or_calib
    elif params_or_calib is not None and hasattr(params_or_calib, "shape"):
        params, calib = None, params_or_calib   # a bare calibration batch
    if calib is not None and not quantize:
        raise ValueError(
            "a calibration batch was provided but "
            "spec.precision.quant='none' — set quant='int8' or drop the "
            "batch")
    if isinstance(params, QuantizedCNNParams) and not quantize:
        raise ValueError(
            "params are QuantizedCNNParams but spec.precision.quant="
            "'none' — compile with Precision(quant='int8')")
    if params is None:
        params = init_cnn_params(key if key is not None
                                 else jax.random.key(0), rcfg)

    # -- pre-seed from a committed plan table ------------------------------
    if plan_path is not None:
        plans = PlanTable.load(plan_path)
    if plans is not None:
        plans.seed()

    # -- compile: calibration, DSE, stage planning, mesh -------------------
    sweeps_before = autotune.sweep_stats()
    # repro: allow[RPA102] compile-track trace spans price real sweep time
    t0 = _time.perf_counter()
    with autotune.record_lookups() as rec:
        if quantize and not isinstance(params, QuantizedCNNParams):
            if calib is None:
                # the serving default: a deterministic synthetic batch
                # from the request distribution (rng(123), as the CLI
                # always did)
                rng = np.random.default_rng(123)
                calib = jnp.asarray(rng.standard_normal(
                    (rcfg.calib, rcfg.input_hw, rcfg.input_hw,
                     rcfg.input_ch)).astype(np.float32))
            params = calibrate_cnn(params, calib, rcfg)
        quant = isinstance(params, QuantizedCNNParams)

        group_plans: Dict[Tuple[int, ...], Any] = {}
        if spec.use_pallas:
            group_plans = resolve_group_plans(
                rcfg, spec.serving.batch, spec.run_dtype)
            _count_conv_paths(rcfg, spec.serving.batch, spec.run_dtype,
                              group_plans)
            R, S = spec.placement.replicas, spec.placement.pp_stages
            if R > 1 and S == 1:
                # the packed (R * batch) super-batch's tuned plans are
                # part of a dp compile's plan table
                resolve_group_plans(rcfg, R * spec.serving.batch,
                                    spec.run_dtype)

        engine = None
        if with_engine:
            from repro.serve.engine import ServeEngine
            # stage planning (incl. the GPipe microbatch sweep) and mesh
            # construction happen HERE, inside the compile
            engine = ServeEngine.from_spec(rcfg, params, spec,
                                           plans=group_plans)

    sweeps_after = autotune.sweep_stats()
    sweep_delta = {k: sweeps_after[k] - sweeps_before[k]
                   for k in sorted(sweeps_after)}
    if trace is not None:
        from repro.obs.trace import CAT_COMPILE, COMPILE_TRACK
        # repro: allow[RPA102] compile-track trace spans price real sweep time
        trace.span("sweep", 0.0, _time.perf_counter() - t0,
                   track=COMPILE_TRACK, cat=CAT_COMPILE,
                   args={"lookups": {"conv": len(rec["conv"]),
                                     "gemm": len(rec["gemm"])},
                         **sweep_delta})

    if plans is not None:
        # a seeded compile re-captures the SAME plans: carry the seed
        # table's provenance AND measurements verbatim so save -> load
        # -> re-compile -> save stays byte-identical (the artifact
        # round-trip contract). No profiler runs here, measure flag or
        # not — the measurements ARE the artifact.
        table = PlanTable.from_rows(
            rec["conv"], rec["gemm"],
            provenance=plans.provenance).with_measurements(
                plans.measurements())
    else:
        provenance = {
            "sweep_stats": sweep_delta,
            "lookups": {"conv": len(rec["conv"]),
                        "gemm": len(rec["gemm"])},
        }
        table = PlanTable.from_rows(rec["conv"], rec["gemm"],
                                    provenance=provenance)
        if measure:
            from repro.obs.profiler import profile_table
            table = profile_table(table, opts=measure_opts, trace=trace,
                                  t0=t0)
    return CompiledCNN(cfg=rcfg, spec=spec, params=params, quant=quant,
                       group_plans=group_plans, plan_table=table,
                       engine=engine)
