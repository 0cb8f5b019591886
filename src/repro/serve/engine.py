"""Distributed CNN serving engine: one router, a mesh of replicas.

The PipeCNN cascade at fleet scale. Three execution modes behind one
API, selected by ``(replicas, pp_stages)`` over a 2-D ``(data, pipe)``
device mesh:

  * **dp** — data-parallel replicas: each gang-scheduled round drains
    one padded micro-batch per replica, packs them into a super-batch
    and shards it over the mesh "data" axis
    (``parallel.sharding.batch_sharding``, the "batch" rule); every
    replica runs the full batched/int8 Pallas pipeline on its shard.
  * **pp** — pipeline-parallel stages: the network is split into
    roofline-balanced stages (``stage_planner``) resident one-per-device
    on the "pipe" axis; microbatches stream through
    ``pipeline_par.pipeline_forward_stages`` exactly like the paper's
    kernel cascade — stage s computes microbatch m while stage s-1
    computes m+1, activations hopping stages via collective_permute.
  * **hybrid** — DP x PP: the super-batch shards over "data" while every
    data shard streams its rows through the same "pipe" stages (one
    shard_map, see ``pipeline_forward_stages(dp_axis=...)``).

CNN stages change activation shape, so pipeline activations travel in a
canonical flat fp32 buffer (max boundary elements wide); each stage's
branch (``jax.lax.switch`` on the stage index) unflattens its static
input shape, runs its fusion groups (``models.cnn.cnn_forward_stage`` /
``..._stage_quant``), and re-flattens. int8 codes ride the fp32 buffer
exactly (|code| <= 127), keeping the quantized pipeline bit-exact.

Scheduling reuses the single-replica launcher's simulated clock as a
fleet discrete-event loop: arrivals are admitted to the least-loaded
replica queue (``Router``, with SLO admission control), each round's
service time advances the clock once for all concurrently-busy
replicas. ``clock="measured"`` uses wall time (NB: host-platform
"devices" execute serially, so measured DP rounds do not speed up on
CPU); ``clock="modeled"`` uses the same roofline cost model the
autotuner ranks plans with — deterministic, and the basis of the
``fleet_vs_single`` benchmark rows. ``execute=False`` skips the actual
forwards entirely (pure discrete-event simulation; predictions are -1),
which is how the benchmarks model fleets without needing 8 devices.

Resilience (the fault-tolerance layer): ``serve(requests,
faults=FaultSchedule(...))`` injects replica fail/recover events into
the loop. A failed replica loses its in-flight round (those requests
re-dispatch against a per-request retry budget with optional
exponential backoff; an exhausted budget ends as an explicit
``Completion(status="failed")`` — never a stranded request), its queue
is evacuated to the survivors, and the fleet serves degraded gang
rounds until the replica recovers — restore is charged the modeled
latency of reloading the committed ``CompiledCNN`` artifact.
``hot_swap(artifact)`` registers a rolling upgrade the same loop
executes: replicas drain and swap one at a time, evacuated requests
re-dispatch for free (a graceful drain loses no work), and each
completion records which version served it.

Gang rounds are the default; ``scheduler="continuous"`` (with the
modeled clock) swaps the whole loop for the per-request slot scheduler
in ``repro.serve.scheduler``: requests admit and retire individually at
microbatch boundaries, queues work-steal past ``steal_threshold``, and
an ``autoscale`` policy grows/shrinks the fleet against p95-vs-SLO and
utilization signals. See that module's docstring for the semantics.
"""
from __future__ import annotations

import heapq
import itertools
import time
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.config import CNNConfig
from repro.models.cnn import (cnn_forward, cnn_forward_stage,
                              cnn_forward_stage_quant)
from repro.obs.metrics import MetricsRegistry, record_report
from repro.obs.trace import (CAT_REQUEST, FLEET_TRACK, TraceRecorder)
from repro.parallel.pipeline_par import pipeline_forward_stages
from repro.parallel.sharding import batch_sharding, data_parallel
from repro.serve.faults import FaultSchedule
from repro.serve.report import FleetReport, fleet_report
from repro.serve.router import Completion, Request, Router
from repro.serve.stage_planner import StagePlan, plan_stages, total_cost

# (counter key, help) for the per-run serve counters; the key doubles as
# the FleetReport-adjacent name: serve_<key>_total in metric snapshots.
SERVE_COUNTERS = (
    ("done", "requests served ok"),
    ("failed", "retry budget exhausted -> Completion(failed)"),
    ("rejected", "admission-control rejections"),
    ("retries", "lost requests re-dispatched against budget"),
    ("steals", "requests work-stolen across queues"),
    ("failures", "replica fail events that landed"),
    ("recoveries", "replicas restored into dispatch"),
    ("degraded", "rounds served with < replicas alive"),
    ("swapped", "replicas rolled by hot_swap"),
    ("scale_up", "replicas the autoscaler spun up"),
    ("scale_down", "replicas the autoscaler drained out"),
    ("rounds", "gang rounds / microbatch boundaries"),
)


def _serve_obs(trace, metrics, n_replicas, *, scheduler, clock):
    """Normalize the (trace, metrics) pair a serve loop records into.

    Always returns live recorder/registry objects (fresh ones when the
    caller passed None) so the loops instrument unconditionally — the
    overhead is a few appends per event on the modeled clock, which the
    benchmark's trace-overhead row asserts is invisible in modeled rows.
    Tracks are registered up front (fleet first, then each replica) so
    thread ids never depend on event order.
    """
    trace = trace if trace is not None else TraceRecorder()
    metrics = metrics if metrics is not None else MetricsRegistry()
    trace.track(FLEET_TRACK)
    for r in range(n_replicas):
        trace.track(f"replica {r}")
    trace.set_meta("scheduler", scheduler)
    trace.set_meta("clock", clock)
    ctr = {key: metrics.counter(f"serve_{key}_total", help)
           for key, help in SERVE_COUNTERS}
    base = {key: c.value for key, c in ctr.items()}
    hist = metrics.histogram("request_latency_seconds",
                             "ok-completion request latency")
    return trace, metrics, ctr, base, hist

# Modeled artifact-restore cost: a recovering (or hot-swapping) replica
# reloads params + plan table from the committed artifact before
# rejoining dispatch. Charged to the simulated clock at a fixed restore
# bandwidth plus a constant reattach overhead — deterministic, like the
# roofline service times.
RESTORE_BW_BYTES_S = 2e9               # committed-artifact read bandwidth
RESTORE_OVERHEAD_S = 5e-3              # process reattach / jit-cache warm


def params_nbytes(params) -> int:
    """Total bytes of a params pytree (fp32 list or QuantizedCNNParams)
    — the payload of a serialized artifact, hence of a modeled restore."""
    return int(sum(np.asarray(jax.device_get(l)).nbytes
                   for l in jax.tree_util.tree_leaves(params)))


def restore_latency_model(n_bytes: int) -> float:
    """Seconds to restore a replica from an ``n_bytes`` artifact."""
    return n_bytes / RESTORE_BW_BYTES_S + RESTORE_OVERHEAD_S


def _prod(shape) -> int:
    n = 1
    for d in shape:
        n *= d
    return n


def make_stage_branches(params, cfg: CNNConfig, stage_plan: StagePlan, *,
                        use_pallas: bool, quant: bool, maxe: int):
    """One ``buf (rows, maxe) -> buf`` branch per pipeline stage.

    Each branch has static interior shapes (its stage's boundary
    activation); `jax.lax.switch` over the traced stage index dispatches
    among them inside the shard_map body.
    """
    def branch_for(si: int, stage):
        n_in = _prod(stage.in_shape)

        def br(buf):
            x = buf[:, :n_in].reshape(buf.shape[0], *stage.in_shape)
            if quant:
                # interior boundaries carry int8 codes in the fp32
                # buffer (exact: |code| <= 127); the first stage gets
                # the raw fp32 image and quantizes at the network edge
                if si > 0:
                    x = x.astype(jnp.int8)
                out = cnn_forward_stage_quant(params, x, cfg, stage.groups,
                                              use_pallas=use_pallas)
            else:
                out = cnn_forward_stage(params, x, cfg, stage.groups,
                                        use_pallas=use_pallas)
            flat = out.reshape(out.shape[0], -1).astype(jnp.float32)
            return jnp.pad(flat, ((0, 0), (0, maxe - flat.shape[1])))

        return br

    return [branch_for(si, s) for si, s in enumerate(stage_plan.stages)]


def pipeline_logits(params, x: jax.Array, cfg: CNNConfig, mesh,
                    stage_plan: StagePlan, *, n_microbatches: int,
                    use_pallas: bool = True, quant: bool = False,
                    dp_axis: Optional[str] = None,
                    axis: str = "pipe") -> jax.Array:
    """Run a (B, H, W, C) batch through device-resident pipeline stages.

    Returns (B, n_classes) logits — numerically identical to the
    unsharded ``cnn_forward`` (fp32 allclose; int8 bit-exact, since the
    stage slicing changes scheduling, never math). ``B`` must divide
    into ``n_microbatches`` (times the dp_axis size, if given).
    """
    n_out = _prod(stage_plan.stages[-1].out_shape)
    maxe = max(stage_plan.max_boundary_elems(), n_out)
    branches = make_stage_branches(params, cfg, stage_plan,
                                   use_pallas=use_pallas, quant=quant,
                                   maxe=maxe)

    def stage_fn(idx, h):
        return jax.lax.switch(idx, branches, h)

    flat = x.reshape(x.shape[0], -1).astype(jnp.float32)
    flat = jnp.pad(flat, ((0, 0), (0, maxe - flat.shape[1])))
    out = pipeline_forward_stages(stage_fn, flat, mesh, axis=axis,
                                  n_microbatches=n_microbatches,
                                  dp_axis=dp_axis)
    return out[:, :n_out]


class ServeEngine:
    """Routes request traffic onto a mesh of CNN replicas.

    ``params`` may be the fp32 param list or a ``QuantizedCNNParams``
    (the engine auto-detects and serves fixed-point, like
    ``cnn_forward``).
    """

    def __init__(self, cfg: CNNConfig, params, *, batch: int = 8,
                 replicas: int = 1, pp_stages: int = 1,
                 n_microbatches: int = 0, use_pallas: bool = True,
                 clock: str = "measured", max_queue: int = 0,
                 execute: bool = True, retries: int = 0,
                 backoff: float = 0.0, slo: float = 0.0,
                 scheduler: str = "gang", steal_threshold: int = 0,
                 autoscale=None):
        from repro.quant.calibrate import QuantizedCNNParams
        from repro.serve.scheduler import AutoscalePolicy
        if clock not in ("measured", "modeled"):
            raise ValueError(f"unknown clock {clock!r}")
        if retries < 0:
            raise ValueError(f"retries={retries} must be >= 0")
        if backoff < 0 or slo < 0:
            raise ValueError("backoff/slo are seconds >= 0")
        if scheduler not in ("gang", "continuous"):
            raise ValueError(f"unknown scheduler {scheduler!r}: "
                             "gang or continuous")
        if scheduler == "continuous" and clock != "modeled":
            raise ValueError(
                "scheduler='continuous' needs clock='modeled': slot "
                "service and microbatch-boundary times come from the "
                "roofline model, not wall time")
        if steal_threshold < 0:
            raise ValueError(
                f"steal_threshold={steal_threshold} must be >= 0 "
                "(0 = stealing off)")
        if isinstance(autoscale, dict):
            autoscale = AutoscalePolicy(**autoscale)
        if (steal_threshold or autoscale is not None) and \
                scheduler != "continuous":
            raise ValueError(
                "steal_threshold / autoscale only exist under "
                "scheduler='continuous': gang rounds have no per-request "
                "slots to steal or scale")
        if autoscale is not None and not (
                autoscale.min_replicas <= replicas
                <= autoscale.max_replicas):
            raise ValueError(
                f"replicas={replicas} outside the autoscale range "
                f"[{autoscale.min_replicas}, {autoscale.max_replicas}]")
        self.scheduler = scheduler
        self.steal_threshold = int(steal_threshold)
        self.autoscale = autoscale
        self.cfg = cfg
        self.params = params
        self.quant = isinstance(params, QuantizedCNNParams)
        self.dtype = "int8" if self.quant else cfg.dtype
        self.batch = batch
        self.replicas = replicas
        self.pp_stages = pp_stages
        self.use_pallas = use_pallas
        self.clock_mode = clock
        self.execute = execute
        self.retries = int(retries)
        self.backoff = float(backoff)
        self.slo = float(slo)
        R, S = replicas, pp_stages
        if R < 1 or S < 1:
            raise ValueError("replicas and pp_stages must be >= 1")
        if not execute and clock == "measured":
            raise ValueError(
                "execute=False (device-free simulation) has no wall time "
                "to measure; use clock='modeled'")
        self.mode = ("single" if R * S == 1 else
                     "dp" if S == 1 else
                     "pp" if R == 1 else "hybrid")

        # microbatches: GPipe wants M >= S to amortize the bubble, but a
        # larger M shrinks the per-stage microbatch and loses the batch
        # amortization the conv plans are tuned for — so by default the
        # engine sweeps the divisors of the plan batch and keeps the M
        # minimizing the MODELED round time (the DSE applied to the
        # schedule itself). mb must divide the batch so every microbatch
        # compiles once.
        if S > 1:
            if n_microbatches:
                if batch % n_microbatches:
                    raise ValueError(
                        f"n_microbatches={n_microbatches} must divide the "
                        f"plan batch {batch}")
                cands = [n_microbatches]
            else:
                cands = [d for d in range(1, batch + 1) if batch % d == 0]
            scored = []
            for m in cands:
                sp = plan_stages(cfg, S, batch=batch // m, dtype=self.dtype)
                scored.append((sp.round_time(m), m, sp))
            t_round, self.n_micro, self.stage_plan = min(
                scored, key=lambda c: (c[0], c[1]))
            self.t_round_model = t_round
        else:
            self.n_micro = 1
            self.stage_plan = None
            # one replica's micro-batch; dp replicas run concurrently
            self.t_round_model = total_cost(cfg, batch, dtype=self.dtype)
        self.mb = batch // self.n_micro
        # the elastic fleet pre-builds queues up to max_replicas; the
        # scheduler's active mask decides which ones receive dispatch
        n_queues = (autoscale.max_replicas if autoscale is not None
                    else R)
        self.router = Router(n_queues, batch, max_queue=max_queue)
        self.mesh = None
        # -- version bookkeeping (hot_swap installs version 1, 2, ...) -----
        self.t_restore_model = restore_latency_model(params_nbytes(params))
        self._cur_version = 0
        self._n_versions = 1
        self._versions = {0: dict(params=params, quant=self.quant, cfg=cfg,
                                  stage_plan=self.stage_plan,
                                  t_round=self.t_round_model,
                                  t_restore=self.t_restore_model)}
        self._pending_swap = None
        self._round_fns = {}
        self._round_fn = None
        self._slot_fns = {}
        if execute and self.scheduler == "gang":
            if R * S > 1:
                if jax.device_count() < R * S:
                    hint = ("; on the CPU backend, set XLA_FLAGS="
                            f"--xla_force_host_platform_device_count={R * S}"
                            if jax.default_backend() == "cpu" else "")
                    raise RuntimeError(
                        f"{self.mode} mode needs {R * S} devices, have "
                        f"{jax.device_count()} {jax.default_backend()} "
                        f"device(s){hint}")
                self.mesh = jax.make_mesh(
                    (R, S), ("data", "pipe"),
                    axis_types=(jax.sharding.AxisType.Auto,) * 2)
            self._round_fn = self._round_fns[0] = self._build_round_fn()
        # continuous scheduling needs no mesh: admissions execute as
        # per-replica padded forwards (see _slot_fn), so the fleet can
        # elastically scale past the device count

    @classmethod
    def from_spec(cls, cfg: CNNConfig, params, spec) -> "ServeEngine":
        """Build the engine from a ``repro.pipeline.ExecutionSpec`` —
        the placement/serving sub-specs are the engine's whole
        constructor surface (``compile_cnn`` calls this so the mesh and
        stage plan are resolved at compile time)."""
        return cls(cfg, params, batch=spec.serving.batch,
                   replicas=spec.placement.replicas,
                   pp_stages=spec.placement.pp_stages,
                   n_microbatches=spec.placement.microbatches,
                   use_pallas=spec.use_pallas, clock=spec.serving.clock,
                   max_queue=spec.serving.max_queue,
                   execute=spec.serving.execute,
                   retries=getattr(spec.serving, "retries", 0),
                   backoff=getattr(spec.serving, "backoff", 0.0),
                   slo=getattr(spec.serving, "slo", 0.0),
                   scheduler=getattr(spec.serving, "scheduler", "gang"),
                   steal_threshold=getattr(spec.serving,
                                           "steal_threshold", 0),
                   autoscale=getattr(spec.serving, "autoscale", None))

    # -- forward builders --------------------------------------------------

    def _build_round_fn(self, params=None, quant=None, stage_plan=None,
                        cfg=None):
        """Gang-round fn ``imgs -> preds`` for one params version.

        With no arguments this builds the originally-compiled version;
        ``hot_swap`` builds the replacement's fn from its own params /
        stage plan (same mesh, same microbatch split — only the weights
        and their dtype change under a rolling upgrade).
        """
        params = self.params if params is None else params
        quant = self.quant if quant is None else quant
        cfg = self.cfg if cfg is None else cfg
        R = self.replicas

        # params are jit ARGUMENTS, never closed over: a closure would
        # bake every weight into the program as a constant
        if self.pp_stages == 1:
            def preds(p, imgs):          # (R*batch, H, W, C)
                # repro: allow[RPA201] the shim IS the parity oracle here
                return jnp.argmax(cnn_forward(p, imgs, cfg,
                                              use_pallas=self.use_pallas),
                                  -1)
            if self.mesh is None:
                fn = jax.jit(preds)
                return lambda imgs: fn(params, imgs)
            fn = jax.jit(data_parallel(preds, self.mesh))

            def dp_round(imgs):
                sharded = jax.device_put(
                    imgs, batch_sharding(self.mesh, imgs.shape))
                return fn(params, sharded)
            return dp_round

        sp = self.stage_plan if stage_plan is None else stage_plan

        def pp_fn(p, imgs_flat):        # (n_micro*R*mb, H, W, C)
            logits = pipeline_logits(
                p, imgs_flat, cfg, self.mesh, sp,
                n_microbatches=self.n_micro, use_pallas=self.use_pallas,
                quant=quant, dp_axis="data")
            return jnp.argmax(logits, -1)
        pp = jax.jit(pp_fn)
        return lambda imgs: pp(params, imgs)

    def _slot_fn(self, v: int):
        """Padded single-replica forward ``imgs (batch, ...) -> preds``
        for params version ``v`` — the continuous scheduler's execution
        unit. No mesh: one admission group runs the full (batched/int8)
        pipeline on the default device, row-independent, so predictions
        match ``cnn_forward`` exactly while replica count floats free
        of the device count."""
        if v not in self._slot_fns:
            rec = self._versions[v]
            params, cfg = rec["params"], rec["cfg"]

            def fn(p, imgs):
                # repro: allow[RPA201] the shim IS the parity oracle here
                logits = cnn_forward(p, imgs, cfg,
                                     use_pallas=self.use_pallas)
                return jnp.argmax(logits, -1)
            jitted = jax.jit(fn)
            self._slot_fns[v] = lambda imgs: jitted(params, imgs)
        return self._slot_fns[v]

    def _version_fn(self, v: int):
        if v not in self._round_fns:
            rec = self._versions[v]
            self._round_fns[v] = self._build_round_fn(
                params=rec["params"], quant=rec["quant"],
                stage_plan=rec["stage_plan"], cfg=rec["cfg"])
        return self._round_fns[v]

    def _pack(self, round_items) -> np.ndarray:
        """Super-batch for one gang round.

        dp: replica-major ``(R*batch, ...)``. pp/hybrid: microbatch-major
        ``(n_micro * R * mb, ...)`` so the shard_map's microbatch reshape
        puts replica r's rows on data-shard r of every microbatch.
        """
        shape = (self.cfg.input_hw, self.cfg.input_hw, self.cfg.input_ch)
        per_rep = []
        for _, _, imgs, n_real in round_items:
            if imgs is None:
                per_rep.append(np.zeros((self.batch,) + shape, np.float32))
            else:
                per_rep.append(np.asarray(imgs))
        arr = np.stack(per_rep)                     # (R, batch, ...)
        if self.pp_stages > 1:
            arr = arr.reshape(self.replicas, self.n_micro, self.mb, *shape)
            arr = arr.transpose(1, 0, 2, 3, 4, 5)   # (n_micro, R, mb, ...)
        return arr.reshape(-1, *shape)

    def _unpack_preds(self, preds: np.ndarray) -> np.ndarray:
        """(rounds rows,) -> (R, batch) back in each replica's order."""
        if self.pp_stages > 1:
            p = preds.reshape(self.n_micro, self.replicas, self.mb)
            return p.transpose(1, 0, 2).reshape(self.replicas, self.batch)
        return preds.reshape(self.replicas, self.batch)

    # -- rolling hot swap --------------------------------------------------

    def hot_swap(self, artifact, *, at: float = 0.0) -> int:
        """Register a rolling upgrade to ``artifact``'s params.

        ``artifact`` is a ``CompiledCNN`` (``.quant`` params win over
        ``.params`` when present, matching how it serves) or a bare
        params pytree. The next ``serve`` call executes the roll inside
        its discrete-event loop, starting at simulated time ``at``:
        replicas leave dispatch one at a time, finish their in-flight
        round (a graceful drain — evacuated queue entries re-dispatch
        WITHOUT consuming retry budget, so no request is ever dropped by
        an upgrade), pay the modeled artifact-restore latency, and
        rejoin serving the new version. Completions record the serving
        ``version``; once every replica has rolled, the engine adopts
        the new params as its compiled state. Returns the version id.
        """
        from repro.quant.calibrate import QuantizedCNNParams
        if self._pending_swap is not None:
            raise RuntimeError("a hot_swap is already registered; serve a "
                               "stream to complete it first")
        new_cfg = getattr(artifact, "cfg", self.cfg)
        new_params = getattr(artifact, "params", artifact)
        for f in ("input_hw", "input_ch", "n_classes"):
            if getattr(new_cfg, f) != getattr(self.cfg, f):
                raise ValueError(
                    f"hot_swap artifact is incompatible with the serving "
                    f"fleet: {f}={getattr(new_cfg, f)} vs "
                    f"{getattr(self.cfg, f)}")
        quant = isinstance(new_params, QuantizedCNNParams)
        dtype = "int8" if quant else new_cfg.dtype
        if self.pp_stages > 1:
            # same microbatch split, rebalanced stages for the new dtype
            sp = plan_stages(new_cfg, self.pp_stages, batch=self.mb,
                             dtype=dtype)
            t_round = sp.round_time(self.n_micro)
        else:
            sp = None
            t_round = total_cost(new_cfg, self.batch, dtype=dtype)
        v = self._n_versions
        self._n_versions += 1
        self._versions[v] = dict(params=new_params, quant=quant,
                                 cfg=new_cfg, stage_plan=sp,
                                 t_round=t_round,
                                 t_restore=restore_latency_model(
                                     params_nbytes(new_params)))
        self._pending_swap = {"state": "armed", "at": float(at),
                              "version": v,
                              "t_restore": self._versions[v]["t_restore"],
                              "todo": [], "current": None}
        return v

    def _adopt_version(self, v: int) -> None:
        """Make version ``v`` the engine's compiled state (the roll is
        complete: subsequent ``serve`` calls start fully on ``v``)."""
        rec = self._versions[v]
        self.params = rec["params"]
        self.quant = rec["quant"]
        self.cfg = rec["cfg"]
        self.dtype = "int8" if rec["quant"] else rec["cfg"].dtype
        self.stage_plan = rec["stage_plan"]
        self.t_round_model = rec["t_round"]
        self.t_restore_model = rec["t_restore"]
        self._cur_version = v
        if self.execute:
            self._round_fn = self._version_fn(v)

    # -- the serving loop --------------------------------------------------

    def serve(self, requests: List[Request], *,
              faults: Optional[FaultSchedule] = None,
              trace: Optional[TraceRecorder] = None,
              metrics: Optional[MetricsRegistry] = None
              ) -> Tuple[List[Completion], FleetReport]:
        """Drain a request stream; returns (completions, fleet report).

        ``trace``/``metrics`` (see :mod:`repro.obs`) receive the run's
        typed spans/instants and counter/gauge/histogram streams — pass
        your own to export them, or leave None (the loop records into
        throwaway instances; instrumentation is always on and never
        touches the simulated clock). A registry is per-run: counters
        reconcile against this run's report.

        The discrete-event loop: admit arrivals up to the clock (router
        policy + admission control), gang-drain one padded micro-batch
        per replica, advance the clock by the round's service time —
        concurrent across replicas, exactly the mesh semantics.

        ``faults`` injects replica fail/recover events (see
        ``repro.serve.faults``): a fail that lands inside a round loses
        that replica's in-flight requests — they re-dispatch against
        their per-request retry budget (``retries``, with exponential
        ``backoff`` on re-admission); an exhausted budget becomes an
        explicit ``Completion(status="failed")``. The fleet serves
        degraded rounds over the survivors until recovery (charged the
        modeled artifact-restore latency). A registered ``hot_swap``
        rolls through the same loop. Invariant: every admitted request
        ends as exactly one Completion or one admission rejection —
        never stranded, even if the whole fleet dies.

        With ``scheduler="continuous"`` the whole call is delegated to
        :class:`repro.serve.scheduler.ContinuousScheduler` — same
        contract, but requests are admitted/retired individually at
        microbatch boundaries, work-stolen across queues, and the
        fleet elastically scales when ``autoscale`` is set.
        """
        if self.scheduler == "continuous":
            from repro.serve.scheduler import ContinuousScheduler
            return ContinuousScheduler(self).serve(requests, faults=faults,
                                                   trace=trace,
                                                   metrics=metrics)
        R = self.replicas
        trace, metrics, ctr, ctr0, hist = _serve_obs(
            trace, metrics, R, scheduler="gang", clock=self.clock_mode)
        if faults is not None:
            faults.validate_for(R)
        router = self.router
        done: List[Completion] = []
        busy = [0.0] * R
        clock = 0.0
        pending = sorted(requests, key=lambda r: r.t_arrival)
        compiled_vs = set()

        up = [True] * R
        version = [self._cur_version] * R
        attempts = {}                   # rid -> losses charged so far
        retry_q: list = []              # (t_ready, seq, Request)
        events: list = []               # (t, seq, kind, replica)
        seq = itertools.count()
        fail_t = {}                     # replica -> time its failure landed
        ttr: List[float] = []
        swapped = set()

        fault_it = iter(faults) if faults is not None else iter(())
        next_fault = next(fault_it, None)

        def pull_faults(t):
            # materialize schedule events up to t (lazy: MTBF streams
            # are infinite); a recovery becomes an "up" event only after
            # the modeled restore of the artifact the replica will load
            nonlocal next_fault
            while next_fault is not None and next_fault.t <= t:
                e, next_fault = next_fault, next(fault_it, None)
                if e.kind == "fail":
                    heapq.heappush(events,
                                   (e.t, next(seq), "fail", e.replica))
                else:
                    t_up = e.t + self._versions[
                        version[e.replica]]["t_restore"]
                    heapq.heappush(events,
                                   (t_up, next(seq), "up", e.replica))

        def readmit(req, t, charge=True):
            # lost/evacuated-by-failure requests consume retry budget;
            # a graceful swap drain re-admits for free (charge=False)
            if not charge:
                heapq.heappush(retry_q, (t, next(seq), req))
                return
            a = attempts.get(req.rid, 0) + 1
            attempts[req.rid] = a
            if a > self.retries:
                done.append(Completion(
                    rid=req.rid, pred=-1, t_arrival=req.t_arrival,
                    t_done=t, replica=-1, status="failed",
                    attempts=a - 1))
                ctr["failed"].inc()
                trace.instant("failed", t, cat=CAT_REQUEST,
                              args={"rid": req.rid, "attempts": a - 1})
                return
            ctr["retries"].inc()
            trace.instant("retry", t, cat=CAT_REQUEST,
                          args={"rid": req.rid, "attempt": a})
            delay = self.backoff * (2 ** (a - 1)) if self.backoff else 0.0
            heapq.heappush(retry_q, (t + delay, next(seq), req))

        def note_dispatch(req, ok, t):
            # the router decided: enqueue lands on the chosen replica's
            # track, an admission rejection is a fleet-level instant
            if ok:
                trace.instant("enqueue", t, cat=CAT_REQUEST,
                              track=f"replica {router.last_replica}",
                              args={"rid": req.rid})
            else:
                ctr["rejected"].inc()
                trace.instant("reject", t, cat=CAT_REQUEST,
                              args={"rid": req.rid})

        def start_next_swap(t):
            sw = self._pending_swap
            while sw["todo"] and sw["current"] is None:
                r = sw["todo"].pop(0)
                if not up[r]:
                    # a down replica restores from the new artifact when
                    # its recovery lands — no drain needed
                    version[r] = sw["version"]
                    swapped.add(r)
                    ctr["swapped"].inc()
                    trace.instant("hot_swap", t,
                                  args={"replica": r,
                                        "version": sw["version"]})
                    continue
                up[r] = False
                for req in router.evacuate(r):
                    readmit(req, t, charge=False)
                heapq.heappush(events,
                               (t + sw["t_restore"], next(seq),
                                "swapped", r))
                sw["current"] = r
            if not sw["todo"] and sw["current"] is None:
                sw["state"] = "done"

        def maybe_start_swap(t):
            sw = self._pending_swap
            if sw is None or sw["state"] != "armed" or t < sw["at"]:
                return
            sw["state"] = "rolling"
            sw["todo"] = list(range(R))
            sw["current"] = None
            start_next_swap(t)

        def handle_event(kind, r, t_e, serving=None):
            sw = self._pending_swap
            if kind == "fail":
                if not up[r]:
                    return              # already down (restoring/swapping)
                up[r] = False
                ctr["failures"].inc()
                trace.instant("fail", t_e, args={"replica": r})
                fail_t[r] = t_e
                if serving is not None and r not in serving["lost"]:
                    take = serving["take"].get(r) or ()
                    if take:            # the in-flight round is lost
                        serving["lost"].add(r)
                        busy[r] += t_e - serving["t0"]
                        trace.span("round", serving["t0"], t_e,
                                   track=f"replica {r}",
                                   args={"aborted": True})
                        for req in take:
                            readmit(req, t_e)
                for req in router.evacuate(r):
                    readmit(req, t_e)
            elif kind == "up":
                if up[r]:
                    return
                if sw is not None and sw.get("current") == r:
                    return              # the swap's restore owns r
                up[r] = True
                ctr["recoveries"].inc()
                trace.instant("recover", t_e, args={"replica": r})
                if r in fail_t:
                    ttr.append(t_e - fail_t.pop(r))
            elif kind == "swapped":
                version[r] = sw["version"]
                up[r] = True
                swapped.add(r)
                ctr["swapped"].inc()
                trace.instant("hot_swap", t_e,
                              args={"replica": r, "version": sw["version"]})
                fail_t.pop(r, None)
                sw["current"] = None
                start_next_swap(t_e)

        while True:
            pull_faults(clock)
            while events and events[0][0] <= clock:
                t_e, _, kind, r = heapq.heappop(events)
                handle_event(kind, r, t_e)
            maybe_start_swap(clock)
            if any(up):
                while pending and pending[0].t_arrival <= clock:
                    req = pending.pop(0)
                    note_dispatch(req, router.dispatch(req, up), clock)
                while retry_q and retry_q[0][0] <= clock:
                    _, _, req = heapq.heappop(retry_q)
                    note_dispatch(req, router.dispatch(req, up), clock)
            if not router.backlog():
                if not pending and not retry_q:
                    break
                # outstanding work, nothing dispatchable: jump the clock
                # to whatever unblocks first (all candidates are > clock:
                # admission above exhausted everything due, pull_faults
                # everything scheduled)
                cands = []
                if any(up):
                    if pending:
                        cands.append(pending[0].t_arrival)
                    if retry_q:
                        cands.append(retry_q[0][0])
                if events:
                    cands.append(events[0][0])
                if next_fault is not None:
                    cands.append(next_fault.t)
                if not cands:
                    # dead fleet, no recovery scheduled: fail every
                    # outstanding request explicitly — none stranded
                    for req in pending + [e[2] for e in retry_q]:
                        t_f = max(clock, req.t_arrival)
                        done.append(Completion(
                            rid=req.rid, pred=-1,
                            t_arrival=req.t_arrival,
                            t_done=t_f, replica=-1,
                            status="failed",
                            attempts=attempts.get(req.rid, 0)))
                        ctr["failed"].inc()
                        trace.instant("failed", t_f, cat=CAT_REQUEST,
                                      args={"rid": req.rid,
                                            "dead_fleet": True})
                    pending, retry_q = [], []
                    break
                clock = max(clock, min(cands))
                continue
            # ---- one gang round over the surviving replica set ----------
            round_items = router.drain_round(up)
            up_at_drain = list(up)
            version_at_drain = list(version)
            need = sorted({version_at_drain[r]
                           for r, _, _, n_real in round_items if n_real})
            t_wall = 0.0
            if self.execute:
                imgs = jnp.asarray(self._pack(round_items))
                for v in need:
                    fn = self._version_fn(v)
                    if v not in compiled_vs:   # compile outside the clock
                        np.asarray(fn(imgs))
                        compiled_vs.add(v)
                # repro: allow[RPA102] the measured clock measures
                t0 = time.perf_counter()
                preds_by_v = {v: self._unpack_preds(
                    np.asarray(self._round_fns[v](imgs))) for v in need}
                # repro: allow[RPA102] the measured clock measures
                t_wall = time.perf_counter() - t0
            else:
                preds_by_v = {v: np.full((R, self.batch), -1)
                              for v in need}
            # a gang round is as slow as its slowest co-scheduled
            # request: a cost>1 straggler multiplies the whole round
            # (all-default costs leave modeled rows unchanged)
            cost_mult = max([1.0] + [req.cost
                                     for _, take, _, _ in round_items
                                     for req in take])
            t_service = (max(self._versions[v]["t_round"] for v in need)
                         * cost_mult
                         if self.clock_mode == "modeled" else t_wall)
            t_end = clock + t_service
            ctr["rounds"].inc()
            if not all(up_at_drain):
                ctr["degraded"].inc()
            # fault/swap events landing inside (clock, t_end] hit the
            # round in flight: a failing replica's take is lost mid-round
            serving = {"t0": clock, "lost": set(),
                       "take": {r: take for r, take, _, _ in round_items}}
            pull_faults(t_end)
            while events and events[0][0] <= t_end:
                t_e, _, kind, r = heapq.heappop(events)
                handle_event(kind, r, t_e, serving=serving)
            lost = serving["lost"]
            any_real = any(n for _, _, _, n in round_items)
            for r, take, _, n_real in round_items:
                if r in lost:
                    continue
                if self.pp_stages > 1:
                    # every up replica's devices compute the padded
                    # super-batch rows of a pp/hybrid round, real rows
                    # or not — utilization must say so
                    if up_at_drain[r] and any_real:
                        busy[r] += t_service
                elif n_real:
                    busy[r] += t_service
                if not take:            # idle/down replica this round
                    continue
                v = version_at_drain[r]
                trace.span("round", clock, t_end, track=f"replica {r}",
                           args={"version": v, "n_real": n_real})
                for req, pred in zip(take, preds_by_v[v][r][:n_real]):
                    done.append(Completion(
                        rid=req.rid, pred=int(pred),
                        t_arrival=req.t_arrival, t_done=t_end, replica=r,
                        version=v, attempts=attempts.get(req.rid, 0)))
                    ctr["done"].inc()
                    hist.observe(t_end - req.t_arrival)
                    trace.span("request", clock, t_end,
                               track=f"replica {r}", cat=CAT_REQUEST,
                               args={"rid": req.rid, "version": v,
                                     "attempts": attempts.get(req.rid, 0)})
            clock = t_end

        sw = self._pending_swap
        if sw is not None:
            # the stream ended before the roll finished: finalize the
            # remaining version flips without extending the makespan
            for r in range(R):
                if r not in swapped:
                    swapped.add(r)
                    ctr["swapped"].inc()
                    trace.instant("hot_swap", clock,
                                  args={"replica": r,
                                        "version": sw["version"]})
            self._adopt_version(sw["version"])
            self._pending_swap = None
        # the report reads this run's deltas from the registry — one
        # source of truth for counters, snapshot and report alike
        n_of = {k: c.value - ctr0[k] for k, c in ctr.items()}
        metrics.gauge("fleet_replicas_serving",
                      "up replicas at run end").set(sum(up))
        rep = fleet_report(
            done, router.rejected, mode=self.mode, replicas=self.replicas,
            pp_stages=self.pp_stages, batch=self.batch,
            clock=self.clock_mode, rounds=n_of["rounds"], busy_s=busy,
            makespan_s=clock,
            bubble_fraction=(self.stage_plan.bubble(self.n_micro)
                             if self.stage_plan else 0.0),
            n_retries=n_of["retries"], n_failures=n_of["failures"],
            n_recoveries=n_of["recoveries"],
            degraded_rounds=n_of["degraded"],
            time_to_recover_s=ttr, n_swapped=n_of["swapped"],
            slo_s=self.slo)
        record_report(metrics, rep)
        return done, rep
