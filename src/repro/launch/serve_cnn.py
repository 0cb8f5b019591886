"""CNN serving launcher — a thin CLI over ``repro.pipeline``.

PipeCNN is an inference accelerator; this launcher is its serving
scenario at fleet scale. PR 2 added the single-replica micro-batching
queue (requests padded to the autotuned plan batch, batched-FC weight
reuse); PR 3 added fixed-point serving (``--quant int8``); PR 4 moved
the queue/clock machinery into the distributed engine
(``repro.serve.ServeEngine``); PR 5 made the flags an
:class:`~repro.pipeline.ExecutionSpec` compiled ONCE into a
:class:`~repro.pipeline.CompiledCNN` (calibration, DSE plans, stage
partition and mesh all resolved before the first request), so this file
is argument parsing plus a report printer. The three execution modes
map to two flags:

  * ``--replicas N``  — N data-parallel replicas over the mesh "data"
    axis (each runs the full batched/int8 Pallas pipeline);
  * ``--pp-stages S`` — the network split into S roofline-balanced
    pipeline stages streamed GPipe-style over the mesh "pipe" axis;
  * both > 1         — hybrid DP x PP on the 2-D mesh.

PR 7 adds the scheduling axis: ``--scheduler continuous`` (with
``--clock modeled``) swaps gang rounds for per-request batch slots
admitted/retired at microbatch boundaries (``repro.serve.scheduler``),
``--steal-threshold`` turns on cross-replica work stealing, and
``--autoscale`` (with the ``--min-replicas``/``--max-replicas``/
``--scale-interval``/``--scale-cooldown``/``--util-high``/``--util-low``
knobs) lets the fleet elastically scale against p95-vs-SLO and
utilization signals. ``--straggler-every``/``--straggler-cost`` salt
the synthetic trace with heavy requests — the pathology that separates
the two schedulers.

PR 8 adds the observability surface: ``--trace-out`` records the run as
Chrome trace-event JSON (per-replica tracks of request/round spans plus
fleet instants — open in Perfetto), ``--metrics-out`` snapshots the
:class:`~repro.obs.MetricsRegistry` the serve loop feeds (``.prom``
suffix for Prometheus text format), and ``--report-json`` dumps
``FleetReport.to_dict()``. All three are derived from the same counters
the report prints, so ``repro.obs.validate`` can reconcile them exactly.

On a TPU the Pallas kernels compile with Mosaic; on any other backend
they run in interpret mode. ``JAX_COMPILATION_CACHE_DIR`` places the
persistent compilation cache (default ``<checkout>/.jax_cache``).
Multi-device runs on CPU need forced host devices, e.g.::

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      PYTHONPATH=src python -m repro.launch.serve_cnn --arch alexnet \
      --smoke --replicas 4 [--pp-stages 2] [--quant int8]

``Request``/``Completion``/``MicroBatcher``/``latency_report`` are
re-exported from ``repro.serve`` for backwards compatibility.
"""
from __future__ import annotations

import argparse
from typing import List

import jax
import numpy as np

from repro.configs import get_config
from repro.core.config import CNNConfig, flops_per_image
from repro.kernels import autotune
from repro.kernels.mode import get_interpret
from repro.pipeline import (ExecutionSpec, Placement, Precision, Serving,
                            Tiling, compile_cnn)
from repro.serve import (Completion, FaultSchedule,  # noqa: F401
                         MicroBatcher, Request, ServeEngine,
                         latency_report)


def synthetic_requests(n: int, hw: int, ch: int, rate: float,
                       seed: int = 0, straggler_every: int = 0,
                       straggler_cost: float = 4.0) -> List[Request]:
    """n requests with exponential inter-arrival times (mean 1/rate s).

    ``straggler_every`` > 0 marks every k-th request as a straggler
    with relative service weight ``straggler_cost`` (the modeled clock
    charges it that multiple of a round) — under gang scheduling it
    stalls its whole co-scheduled round, under continuous batching only
    its own slot.
    """
    rng = np.random.default_rng(seed)
    t = 0.0
    out = []
    for i in range(n):
        t += rng.exponential(1.0 / rate)
        cost = (straggler_cost
                if straggler_every and i % straggler_every == 0 else 1.0)
        out.append(Request(rid=i, t_arrival=t, cost=cost,
                           image=rng.standard_normal(
                               (hw, hw, ch)).astype(np.float32)))
    return out


def serve(cfg: CNNConfig, params, requests: List[Request], *,
          batch: int, use_pallas: bool, replicas: int = 1,
          pp_stages: int = 1, clock: str = "measured",
          max_queue: int = 0) -> List[Completion]:
    """DEPRECATION SHIM (the PR 2 launcher API): compile-once, serve,
    return just the completions. New code should call
    ``repro.pipeline.compile_cnn(cfg, spec, params).serve(requests)``.
    """
    from repro.quant.calibrate import QuantizedCNNParams
    if cfg.quant == "int8" and not isinstance(params, QuantizedCNNParams):
        # the legacy engine errored at first-round trace for this
        # combination; keep it an error rather than silently calibrating
        # on synthetic noise (compile_cnn's CLI behaviour)
        raise ValueError(
            "cfg.quant='int8' but params are not QuantizedCNNParams; "
            "run repro.quant.calibrate_cnn first, or use "
            "repro.pipeline.compile_cnn which owns calibration")
    # lift the cfg's precision/tiling knobs intact; placement/serving
    # come from this function's own arguments (matching the legacy
    # engine, which also ignored cfg.serve_microbatches here) — building
    # the spec from exactly the fields that will run means validation
    # can't trip on legacy knobs a plain serve never consults
    spec = ExecutionSpec(
        precision=Precision(dtype=cfg.dtype, quant=cfg.quant,
                            calib=cfg.calib),
        tiling=Tiling(autotune=cfg.autotune, vmem_budget=cfg.vmem_budget,
                      vec_size=cfg.vec_size, cu_num=cfg.cu_num,
                      oh_blk=cfg.oh_blk, b_blk=cfg.b_blk),
        placement=Placement(replicas=replicas, pp_stages=pp_stages),
        serving=Serving(batch=batch, clock=clock, max_queue=max_queue),
        use_pallas=use_pallas)
    rep = compile_cnn(cfg, spec, params).serve(requests)
    return rep.completions


def default_request_count(batch: int, replicas: int = 1) -> int:
    """Two full micro-batches per replica plus a deliberately
    non-dividing remainder, so every serving demo exercises the
    pad-to-plan path."""
    return 2 * batch * replicas + 3


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="alexnet",
                    help="a CNN config id (alexnet, vgg16)")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced channel counts (CPU-friendly)")
    ap.add_argument("--batch", type=int, default=8,
                    help="micro-batch each replica queue pads requests to")
    ap.add_argument("--replicas", type=int, default=0,
                    help="data-parallel replicas over the mesh 'data' "
                         "axis (default: CNNConfig.replicas)")
    ap.add_argument("--pp-stages", type=int, default=0,
                    help="pipeline stages over the mesh 'pipe' axis "
                         "(default: CNNConfig.pp_stages)")
    ap.add_argument("--microbatches", type=int, default=0,
                    help="GPipe microbatches per pipeline round (0=auto)")
    ap.add_argument("--clock", choices=("measured", "modeled"),
                    default="measured",
                    help="advance the simulated clock by wall time or by "
                         "the roofline cost model (deterministic)")
    ap.add_argument("--max-queue", type=int, default=0,
                    help="admission control: reject when every replica "
                         "queue holds this many requests (0 = unbounded)")
    ap.add_argument("--requests", type=int, default=0,
                    help="total synthetic requests (default "
                         "2*batch*replicas + 3, a non-dividing count)")
    ap.add_argument("--rate", type=float, default=200.0,
                    help="simulated request arrival rate (req/s)")
    ap.add_argument("--no-pallas", action="store_true",
                    help="serve through the XLA reference path instead of "
                         "the fused Pallas pipeline")
    ap.add_argument("--quant", choices=("none", "int8"), default="none",
                    help="serve in fixed-point: calibrate on a synthetic "
                         "batch, then run the int8 kernel pipeline")
    ap.add_argument("--calib", type=int, default=8,
                    help="calibration images for --quant int8")
    # -- chaos / resilience flags -----------------------------------------
    ap.add_argument("--fail-at", type=float, default=None,
                    help="inject a replica failure at this simulated "
                         "second (deterministic chaos)")
    ap.add_argument("--recover-at", type=float, default=None,
                    help="recover the failed replica at this simulated "
                         "second (requires --fail-at; restore latency is "
                         "charged on top)")
    ap.add_argument("--fail-replica", type=int, default=0,
                    help="which replica --fail-at kills")
    ap.add_argument("--mtbf", type=float, default=0.0,
                    help="stochastic chaos: mean time between failures "
                         "per replica (seconds; needs --mttr)")
    ap.add_argument("--mttr", type=float, default=0.0,
                    help="mean time to repair for --mtbf mode (seconds)")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed for --mtbf chaos (byte-reproducible)")
    ap.add_argument("--retries", type=int, default=0,
                    help="per-request re-dispatch budget after a replica "
                         "failure (exceeded -> Completion status='failed')")
    ap.add_argument("--backoff", type=float, default=0.0,
                    help="base exponential-backoff delay (s) before a "
                         "retried request re-enters admission")
    ap.add_argument("--slo", type=float, default=0.0,
                    help="per-request latency bound (s); the report "
                         "counts violations (0 = off) and the "
                         "autoscaler scales on windowed p95 vs it")
    # -- continuous batching / elastic fleet flags -------------------------
    ap.add_argument("--scheduler", choices=("gang", "continuous"),
                    default="gang",
                    help="unit of scheduling: padded gang rounds, or "
                         "per-request slots admitted/retired at "
                         "microbatch boundaries (needs --clock modeled)")
    ap.add_argument("--steal-threshold", type=int, default=0,
                    help="continuous only: steal one queued request per "
                         "boundary when queue-depth skew exceeds this "
                         "(each steal charges the retry budget; 0 = off)")
    ap.add_argument("--autoscale", action="store_true",
                    help="continuous only: elastically scale replicas "
                         "between --min/--max-replicas on p95-vs-SLO "
                         "and utilization signals")
    ap.add_argument("--min-replicas", type=int, default=0,
                    help="autoscale floor (default: initial --replicas)")
    ap.add_argument("--max-replicas", type=int, default=0,
                    help="autoscale ceiling (default: 2x --replicas)")
    ap.add_argument("--scale-interval", type=float, default=0.05,
                    help="seconds between autoscale policy evaluations")
    ap.add_argument("--scale-cooldown", type=float, default=0.0,
                    help="minimum seconds between scaling decisions")
    ap.add_argument("--util-high", type=float, default=0.85,
                    help="scale up when fleet load (slots+backlog over "
                         "capacity) exceeds this")
    ap.add_argument("--util-low", type=float, default=0.30,
                    help="scale down (graceful drain) when fleet load "
                         "falls below this")
    ap.add_argument("--straggler-every", type=int, default=0,
                    help="mark every k-th synthetic request a straggler "
                         "(0 = none)")
    ap.add_argument("--straggler-cost", type=float, default=4.0,
                    help="relative service weight of a straggler request")
    # -- observability flags ------------------------------------------------
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write a Chrome trace-event JSON of the run "
                         "(load in Perfetto / chrome://tracing); "
                         "byte-deterministic under --clock modeled")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write the metrics snapshot (counters, gauges, "
                         "latency histograms); a .prom suffix switches "
                         "to Prometheus text exposition format")
    ap.add_argument("--report-json", default=None, metavar="PATH",
                    help="dump FleetReport.to_dict() as JSON")
    # -- measured refinement / drift flags (PR 9) ---------------------------
    ap.add_argument("--measure", action="store_true",
                    help="measured refinement at compile time: profile "
                         "every resolved plan (repro.obs.profiler) and "
                         "record t_measured + the backend fingerprint "
                         "into the plan table (format 3)")
    ap.add_argument("--measure-repeats", type=int, default=3,
                    help="timing samples per plan for --measure "
                         "(trimmed-mean over these)")
    ap.add_argument("--plan-out", default=None, metavar="PATH",
                    help="write the compiled plan table JSON (carries "
                         "measurements under --measure)")
    ap.add_argument("--drift-out", default=None, metavar="PATH",
                    help="write the measured-vs-modeled drift report "
                         "JSON (python -m repro.obs.drift format; "
                         "meaningful with --measure)")
    ap.add_argument("--verify", action="store_true",
                    help="static pre-flight (repro.analysis): re-prove "
                         "VMEM budgets, tile geometry, spec consistency "
                         "and fusion-group coverage of the compiled "
                         "plan table, and refuse to serve on findings")
    args = ap.parse_args()
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()

    cfg = get_config(args.arch)
    if not isinstance(cfg, CNNConfig):
        raise SystemExit(f"--arch {args.arch} is not a CNN config; "
                         "use repro.launch.serve for the LM family")
    if args.smoke:
        cfg = cfg.smoke()
    replicas = args.replicas or cfg.replicas
    pp_stages = args.pp_stages or cfg.pp_stages
    n_req = args.requests or default_request_count(args.batch, replicas)
    use_pallas = not args.no_pallas

    # the flags ARE the spec: one validated object, compiled once —
    # contradictions (e.g. microbatches without stages) fail here, not
    # five frames into pallas tracing. The cfg's own dtype/tiling knobs
    # are lifted intact (the spec is authoritative, so defaulting them
    # would silently overwrite a customized config)
    autoscale = None
    if args.autoscale:
        from repro.pipeline import AutoscalePolicy
        autoscale = AutoscalePolicy(
            min_replicas=args.min_replicas or replicas,
            max_replicas=args.max_replicas or 2 * replicas,
            interval=args.scale_interval, cooldown=args.scale_cooldown,
            util_high=args.util_high, util_low=args.util_low)
    spec = ExecutionSpec(
        precision=Precision(dtype=cfg.dtype, quant=args.quant,
                            calib=args.calib),
        tiling=Tiling(autotune=cfg.autotune, vmem_budget=cfg.vmem_budget,
                      vec_size=cfg.vec_size, cu_num=cfg.cu_num,
                      oh_blk=cfg.oh_blk, b_blk=cfg.b_blk),
        placement=Placement(replicas=replicas, pp_stages=pp_stages,
                            microbatches=args.microbatches),
        serving=Serving(batch=args.batch, clock=args.clock,
                        max_queue=args.max_queue, retries=args.retries,
                        backoff=args.backoff, slo=args.slo,
                        scheduler=args.scheduler,
                        steal_threshold=args.steal_threshold,
                        autoscale=autoscale),
        use_pallas=use_pallas)
    faults = None
    if args.mtbf and args.fail_at is not None:
        raise SystemExit("--fail-at (deterministic) and --mtbf "
                         "(stochastic) are exclusive chaos modes")
    if args.fail_at is not None:
        faults = FaultSchedule.at(args.fail_at, args.recover_at,
                                  replica=args.fail_replica)
    elif args.recover_at is not None:
        raise SystemExit("--recover-at requires --fail-at")
    elif args.mtbf:
        faults = FaultSchedule.mtbf(args.mtbf, args.mttr, replicas,
                                    seed=args.seed)
    trace = metrics = None
    if args.trace_out or args.metrics_out:
        from repro.obs import MetricsRegistry, TraceRecorder
        trace = TraceRecorder() if args.trace_out else None
        metrics = MetricsRegistry() if args.metrics_out else None
    measure_opts = None
    if args.measure:
        from repro.obs import MeasureOptions
        measure_opts = MeasureOptions(repeats=args.measure_repeats)
    compiled = compile_cnn(cfg, spec, measure=args.measure,
                           measure_opts=measure_opts, trace=trace)
    if args.verify:
        findings = compiled.verify()
        for f in findings:
            print(f"[serve_cnn] VERIFY {f}")
        if findings:
            raise SystemExit(
                f"[serve_cnn] --verify: {len(findings)} static "
                f"finding(s) — refusing to serve {args.arch!r}")
        print(f"[serve_cnn] --verify: plan table statically verified "
              f"({len(compiled.plan_table)} rows, 0 findings)")
    requests = synthetic_requests(n_req, cfg.input_hw, cfg.input_ch,
                                  args.rate,
                                  straggler_every=args.straggler_every,
                                  straggler_cost=args.straggler_cost)

    if args.quant == "int8":
        qp = compiled.params        # calibrated during the compile phase
        n_conv = sum(1 for l in qp.layers
                     if l is not None and l.kind == "conv")
        print(f"[serve_cnn] int8 calibration: {args.calib} images, "
              f"{n_conv} conv layers quantized (per-channel weights, "
              f"per-tensor activations); input scale "
              f"{qp.in_scale:.3g}")
    if compiled.stage_plan is not None:
        sp = compiled.stage_plan
        engine = compiled.engine
        print(f"[serve_cnn] {pp_stages} pipeline stages "
              f"(balance {sp.balance:.2f}, bubble "
              f"{sp.bubble(engine.n_micro):.0%} at M={engine.n_micro}): "
              + " | ".join(f"s{i}:{len(s.groups)}g "
                           f"{s.t_model * 1e6:.0f}us"
                           for i, s in enumerate(sp.stages)))
    if faults is not None:
        print(f"[serve_cnn] chaos: {faults!r}, retries={args.retries}, "
              f"backoff={args.backoff}s")
    rep = compiled.serve(requests, faults=faults, trace=trace,
                         metrics=metrics)
    # the resilience invariant: every request ends as exactly one
    # completion (ok or explicitly failed) or one admission rejection
    assert len(rep.completions) + rep.n_rejected == n_req, \
        (len(rep.completions), n_req)
    if args.scheduler == "continuous":
        # scale/steal accounting must be self-consistent: one recorded
        # event per decision, and the final fleet size follows from them
        ups = sum(1 for e in rep.scale_events if e["kind"] == "up")
        downs = sum(1 for e in rep.scale_events if e["kind"] == "down")
        assert (ups, downs) == (rep.n_scale_up, rep.n_scale_down), \
            (ups, downs, rep.n_scale_up, rep.n_scale_down)
        assert rep.replicas_final == replicas + ups - downs, \
            (rep.replicas_final, replicas, ups, downs)
        print(f"[serve_cnn] continuous: {rep.n_steals} steals, "
              f"{rep.n_scale_up} scale-ups, {rep.n_scale_down} "
              f"scale-downs, {rep.replicas_final} final replicas, "
              f"mean occupancy "
              + "/".join(f"{o:.0%}" for o in rep.occupancy))
    gops = flops_per_image(compiled.cfg) * rep.throughput / 1e9

    print(f"[serve_cnn] {args.arch}{' (smoke)' if args.smoke else ''}: "
          f"{n_req} requests @ micro-batch {args.batch}, mode "
          f"{compiled.mode} (R={replicas}, S={pp_stages}; "
          f"{'pallas' if use_pallas else 'xla-ref'} path"
          f"{', int8' if args.quant == 'int8' else ''})")
    print(f"[serve_cnn] {rep.summary()}")
    if use_pallas:
        mode = "interpret mode" if get_interpret() else "compiled (Mosaic)"
        print(f"[serve_cnn] Pallas kernels: {mode} on "
              f"{jax.devices()[0].device_kind} "
              f"({jax.default_backend()})")
    print(f"[serve_cnn] aggregate {gops:.2f} GOPS at the reported "
          f"throughput")
    if use_pallas and cfg.autotune:
        tbl = compiled.plans()
        dtype = spec.run_dtype
        rows = [r for r in tbl.conv if r["shape"]["dtype"] == dtype]
        picked = sorted({(r["plan"]["b_blk"], r["plan"]["c_blk"],
                          r["plan"]["m_blk"], r["plan"]["oh_blk"])
                         for r in rows})
        gemm = [r for r in tbl.gemm if r["shape"]["dtype"] == dtype]
        print(f"[serve_cnn] plan table: {len(rows)} conv plans + "
              f"{len(gemm)} GEMM plans compiled ({dtype}); conv "
              f"(b,c,m,oh)_blk points: {picked}")
    if args.plan_out:
        compiled.save_plan(args.plan_out)
        print(f"[serve_cnn] plan table "
              f"({compiled.plan_table.summary()}) -> {args.plan_out}")
    if args.measure or args.drift_out:
        from repro.obs import drift_report, record_drift
        drift = drift_report(compiled.plan_table)
        stats = drift.get("ratio")
        print(f"[serve_cnn] drift: {drift['n_measured']}/"
              f"{drift['n_plans']} plans measured"
              + (f", geomean ratio {stats['geomean']:.3g}x"
                 if stats else ""))
        if metrics is not None and args.measure:
            record_drift(metrics, drift)
        if args.drift_out:
            import json
            with open(args.drift_out, "w") as f:
                json.dump(drift, f, sort_keys=True, indent=1)
                f.write("\n")
            print(f"[serve_cnn] drift report -> {args.drift_out}")
    if trace is not None:
        trace.save(args.trace_out)
        print(f"[serve_cnn] trace: {len(trace)} events -> "
              f"{args.trace_out}")
    if metrics is not None:
        metrics.save(args.metrics_out)
        print(f"[serve_cnn] metrics -> {args.metrics_out}")
    if args.report_json:
        import json
        with open(args.report_json, "w") as f:
            json.dump(rep.to_dict(), f, sort_keys=True, indent=1)
            f.write("\n")
        print(f"[serve_cnn] report -> {args.report_json}")
    print("[serve_cnn] OK")


if __name__ == "__main__":
    main()
