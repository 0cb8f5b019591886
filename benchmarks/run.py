"""Benchmark harness: one entry per paper table/figure + the LM roofline.

Prints ``name,us_per_call,derived`` CSV lines at the end (harness contract)
and writes ``BENCH_conv.json`` (name -> us_per_call + chosen tile plan) so
future PRs can diff conv-pipeline performance machine-readably.

``--check-against PATH`` is the CI perf-regression gate: before
overwriting BENCH_conv.json it compares every freshly modelled layer row
(``*_model``, deterministic roofline times) against the committed
trajectory at PATH and exits non-zero if any layer regressed more than
10%. Measured (wall-clock) rows are noisy and are NOT gated. The
compile phase is tracked too (PR 5): ``{arch}_compile_sweeps_model``
gates the deterministic DSE sweep count of a cold
``repro.pipeline.compile_cnn``, and the warm-recompile row must be
sweep-free (enforced every run, like the int8/fleet invariants).
The measured-plan loop is tracked as well (PR 9): the
``measured_vs_modeled(arch)`` row records per-plan drift between the
analytic roofline and the profiler's wall clock, and its provenance
invariants (full coverage, zero seeded re-measurement, byte-identical
seeded tables) are enforced every run.

Run: PYTHONPATH=src python -m benchmarks.run [--fast]
                                             [--check-against BENCH_conv.json]
"""
from __future__ import annotations

import argparse
import io
import json
import sys
import time
from contextlib import redirect_stdout

BENCH_JSON = "BENCH_conv.json"


def conv_shapes(cfg, b: int = 1) -> list:
    """ConvShape per conv layer of a CNNConfig (fused pool folded in),
    tuned for serving batch ``b``."""
    from repro.kernels import autotune

    out = []
    h, c = cfg.input_hw, cfg.input_ch
    for i, l in enumerate(cfg.layers):
        if l.kind == "conv":
            nxt = cfg.layers[i + 1] if i + 1 < len(cfg.layers) else None
            pool = nxt if nxt is not None and nxt.kind == "pool" else None
            out.append(autotune.ConvShape(
                h=h, w=h, c=c, kh=l.kernel, kw=l.kernel, m=l.out_ch,
                stride=l.stride, pad=l.pad, groups=l.groups,
                pool=(pool.pool if pool else None),
                pool_k=(pool.kernel if pool else 2),
                pool_s=(pool.stride if pool else 2), dtype=cfg.dtype, b=b))
            h = (h + 2 * l.pad - l.kernel) // l.stride + 1
            c = l.out_ch
        elif l.kind == "pool":
            h = (h - l.kernel) // l.stride + 1
    return out


def conv_bench(fast: bool) -> dict:
    """Tiled-conv trajectory numbers for BENCH_conv.json.

    * measured us/call for a smoke-scale fused conv: XLA ref vs the tiled
      Pallas kernel (interpret mode here, so the Pallas number tracks
      kernel-body work, not TPU wall clock)
    * the autotuned plan + modelled roofline time for every AlexNet and
      VGG-16 conv layer
    * a before/after row: the seed's full-height plan vs the tuned tiled
      plan on VGG conv2 (the layer whose full-height accumulator busts
      the 16 MiB VMEM budget)
    """
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.kernels import autotune, ops

    rows: dict = {}

    # -- measured: smoke-scale fused conv, ref vs tiled pallas ------------
    key = jax.random.key(0)
    x = jax.random.normal(key, (1, 32, 32, 8), jnp.float32)
    w = jax.random.normal(key, (3, 3, 8, 16), jnp.float32) * 0.2
    b = jnp.zeros((16,))
    # a 1 MiB budget forces H-tiling (the (8, 128)-padded tiles of this
    # 8-channel layer take ~1.2 MiB per 8 conv rows)
    plan = autotune.plan_for_layer(x.shape, w.shape, pad=1, pool="max",
                                   vmem_budget=1024 * 1024)

    def timed(fn, iters):
        fn().block_until_ready()
        t0 = time.perf_counter()
        for _ in range(iters):
            fn().block_until_ready()
        return (time.perf_counter() - t0) / iters * 1e6

    iters = 2 if fast else 5
    rows["conv_smoke_ref_fused"] = {
        "us_per_call": timed(lambda: ops.fused_conv(
            x, w, b, pad=1, pool="max"), iters), "plan": None}
    rows["conv_smoke_tiled_pallas"] = {
        "us_per_call": timed(lambda: ops.fused_conv(
            x, w, b, pad=1, pool="max", use_pallas=True, plan=plan), iters),
        "plan": plan.to_dict()}

    # -- modelled: autotuned plan per paper conv layer, fp32 AND int8 -----
    # (the paper's Table-1 precision/performance trade: fixed-point
    # quarters the streamed bytes and doubles the MXU op rate, so every
    # bandwidth-bound layer must model at <= 0.5x fp32 — recorded into
    # the int8_vs_fp32 rows and ENFORCED by main(), which exits non-zero
    # if any row's int8_le_half_on_bandwidth_bound flag is false)
    import dataclasses as _dc
    for name in ("alexnet", "vgg16"):
        cfg = get_config(name)
        int8_rows = []
        for conv_i, (shape, q_shape) in enumerate(zip(
                conv_shapes(cfg),
                conv_shapes(_dc.replace(cfg, dtype="int8"))), start=1):
            p = autotune.get_plan(shape, vmem_budget=cfg.vmem_budget)
            rows[f"{name}_conv{conv_i}_model"] = {
                "us_per_call": p.t_model * 1e6, "plan": p.to_dict()}
            q = autotune.get_plan(q_shape, vmem_budget=cfg.vmem_budget)
            rows[f"{name}_conv{conv_i}_int8_model"] = {
                "us_per_call": q.t_model * 1e6, "plan": q.to_dict()}
            tc, tm = autotune.score_plan(shape, p.c_blk, p.m_blk,
                                         p.oh_blk, p.b_blk)
            int8_rows.append({
                "layer": f"conv{conv_i}",
                "fp32_us": p.t_model * 1e6, "int8_us": q.t_model * 1e6,
                "ratio": q.t_model / p.t_model,
                "bandwidth_bound_fp32": tm >= tc})
        bw = [r for r in int8_rows if r["bandwidth_bound_fp32"]]
        rows[f"int8_vs_fp32({name})"] = {
            "layers": int8_rows,
            "int8_le_half_on_bandwidth_bound":
                all(r["ratio"] <= 0.5 for r in bw),
            "n_bandwidth_bound": len(bw)}

    # -- before/after: seed full-height knobs vs tuned tiling -------------
    s = autotune.ConvShape(h=224, w=224, c=64, kh=3, kw=3, m=64, pad=1)
    tc, tm = autotune.score_plan(s, 8, 32, 0)
    tuned = autotune.get_plan(s)
    rows["fused_full_height_vs_tiled(vgg_conv2)"] = {
        "before": {"plan": {"c_blk": 8, "m_blk": 32, "oh_blk": 0},
                   "vmem_bytes": autotune.conv_vmem_bytes(s, 8, 32, 0),
                   "t_model_us": max(tc, tm) * 1e6,
                   "fits_16MiB": autotune.conv_vmem_bytes(s, 8, 32, 0)
                   <= 16 * 2 ** 20},
        "after": {"plan": tuned.to_dict(),
                  "t_model_us": tuned.t_model * 1e6,
                  "fits_16MiB": tuned.vmem_bytes <= 16 * 2 ** 20}}

    # -- batched vs per-image: the PR 2 serving-path fold -----------------
    # Two AlexNet layers at serve batch 8: per-image launches (b_blk
    # pinned to 1) vs the batch-folded grid tuned jointly over
    # (b,c,m,oh)_blk. The folded grid must model NO SLOWER at batch >= 4
    # (acceptance). conv1 is compute-bound (C=3 starves the MXU) so
    # folding only breaks even; conv3 is weight-traffic-bound (13x13
    # spatial, 384 output channels) and folding amortizes each weight
    # fetch over b_blk images — the paper's batched-FC argument on conv.
    acfg = get_config("alexnet")
    shapes_b8 = conv_shapes(acfg, b=8)
    for li, sb in ((1, shapes_b8[0]), (3, shapes_b8[2])):
        folded = autotune.get_plan(sb, vmem_budget=acfg.vmem_budget)
        per_image = min(
            (p for p in autotune.enumerate_plans(sb, acfg.vmem_budget)
             if p.b_blk == 1), key=lambda p: p.t_model)
        rows[f"batched_vs_per_image(alexnet_conv{li},b=8)"] = {
            "per_image": {"plan": per_image.to_dict(),
                          "t_model_us_per_image": per_image.t_model * 1e6},
            "batched": {"plan": folded.to_dict(),
                        "t_model_us_per_image": folded.t_model * 1e6},
            "batched_no_slower": folded.t_model <= per_image.t_model,
            "speedup": per_image.t_model / folded.t_model}
    return rows


def fc_gemm_shapes(cfg, b: int = 8) -> list:
    """GemmShape per FC layer of a CNNConfig at serving batch ``b``
    (boundary shapes come from the stage planner's one shape walk)."""
    from repro.kernels import autotune
    from repro.serve.stage_planner import group_io_shapes

    out = []
    for group, in_shape, out_shape in group_io_shapes(cfg):
        if cfg.layers[group[0]].kind == "fc":
            k = 1
            for d in in_shape:
                k *= d
            out.append(autotune.GemmShape(m=b, k=k, n=out_shape[-1],
                                          dtype=cfg.dtype))
    return out


def fleet_bench(fast: bool) -> dict:
    """Distributed-serving trajectory rows (PR 4).

    * ``{arch}_fc{i}_gemm_model`` — the dtype-aware GEMM DSE plan per
      classifier layer (fp32 and int8), closing the ROADMAP item "int8
      FC plans are untuned";
    * ``{arch}_fleet_{single,dp4,pp4}_model`` — modeled per-image
      service time of the serving engine in each mode, from a
      deterministic discrete-event simulation (modeled clock, no
      devices needed);
    * ``fleet_vs_single(alexnet)`` — the PR acceptance row: 4
      data-parallel replicas must achieve >= 3x aggregate modeled
      throughput vs the single-replica baseline (enforced by main());
    * ``{arch}_fleet_dp4_fail1_model`` — the resilience row (PR 6): the
      same dp4 fleet with one deterministic replica failure mid-burst
      and a later recovery (modeled artifact-restore latency charged),
      retries re-dispatching the losses. Deterministic chaos, so the
      throughput-under-failure cost is a gateable number;
    * ``{arch}_fleet_gang_skew_model`` / ``{arch}_fleet_cb_model`` —
      the scheduler rows (PR 7): the same dp2 fleet fed a skewed trace
      (Poisson-ish arrivals at ~0.8x capacity, every 17th request a
      4x-cost straggler) under gang rounds vs the continuous-batching
      scheduler (per-slot retirement + work stealing);
    * ``cb_vs_gang(alexnet)`` — the PR 7 acceptance row: under that
      skewed trace, continuous batching must beat the gang scheduler's
      p95 latency (enforced by main());
    * ``{arch}_fleet_cb_traced_model`` — the observability row (PR 8):
      the cb simulation re-run with a ``TraceRecorder`` and
      ``MetricsRegistry`` attached. Instrumentation never touches the
      modeled clock, so the traced report must be IDENTICAL to the
      untraced cb row (``traced_identical``, enforced by main()).
    """
    import dataclasses as _dc

    import numpy as np

    from repro.configs import get_config
    from repro.kernels import autotune
    from repro.serve import FaultSchedule, Request, ServeEngine, total_cost

    rows: dict = {}
    BATCH, N_REQ = 8, 96

    def sim(cfg, replicas, pp_stages, faults=None, retries=0):
        # execute=False: pure discrete-event simulation over the roofline
        # cost model — image payloads are never computed, so keep them tiny
        reqs = [Request(rid=i, image=np.zeros((1, 1, 1), np.float32),
                        t_arrival=0.0) for i in range(N_REQ)]
        eng = ServeEngine(cfg, [], batch=BATCH, replicas=replicas,
                          pp_stages=pp_stages, clock="modeled",
                          execute=False, retries=retries)
        done, rep = eng.serve(reqs, faults=faults)
        assert sorted(c.rid for c in done) == list(range(N_REQ))
        return eng, rep

    for name in ("alexnet",) if fast else ("alexnet", "vgg16"):
        cfg = get_config(name)
        for fc_i, (shape, q_shape) in enumerate(zip(
                fc_gemm_shapes(cfg),
                fc_gemm_shapes(_dc.replace(cfg, dtype="int8"))), start=1):
            p = autotune.get_gemm_plan(shape, vmem_budget=cfg.vmem_budget)
            rows[f"{name}_fc{fc_i}_gemm_model"] = {
                "us_per_call": p.t_model * 1e6, "plan": p.to_dict()}
            q = autotune.get_gemm_plan(q_shape, vmem_budget=cfg.vmem_budget)
            rows[f"{name}_fc{fc_i}_int8_gemm_model"] = {
                "us_per_call": q.t_model * 1e6, "plan": q.to_dict()}

        fleet = {}
        for mode, (r, s) in (("single", (1, 1)), ("dp4", (4, 1)),
                             ("pp4", (1, 4))):
            eng, rep = sim(cfg, r, s)
            fleet[mode] = rep
            rows[f"{name}_fleet_{mode}_model"] = {
                "us_per_call": 1e6 / rep.throughput,
                "fleet": {"mode": rep.mode, "replicas": r, "pp_stages": s,
                          "batch": BATCH, "n_micro": eng.n_micro,
                          "throughput_img_s": rep.throughput,
                          "p95_ms": rep.p95_ms}}
        # resilience row: kill replica 0 half a round in, recover it two
        # rounds later (restore latency on top), re-dispatch with budget 2
        t_round = total_cost(cfg, BATCH)
        chaos = FaultSchedule.at(t_round * 0.5, t_round * 2.5, replica=0)
        _, crep = sim(cfg, 4, 1, faults=chaos, retries=2)
        rows[f"{name}_fleet_dp4_fail1_model"] = {
            "us_per_call": 1e6 / crep.throughput,
            "fleet": {"mode": crep.mode, "replicas": 4, "pp_stages": 1,
                      "batch": BATCH, "throughput_img_s": crep.throughput,
                      "p95_ms": crep.p95_ms,
                      "n_failures": crep.n_failures,
                      "n_recoveries": crep.n_recoveries,
                      "n_retries": crep.n_retries,
                      "degraded_rounds": crep.degraded_rounds,
                      "ttr_ms": max(crep.time_to_recover_s) * 1e3
                      if crep.time_to_recover_s else 0.0}}

        rows[f"fleet_vs_single({name})"] = {
            "single_img_s": fleet["single"].throughput,
            "dp4_img_s": fleet["dp4"].throughput,
            "pp4_img_s": fleet["pp4"].throughput,
            "dp4_speedup": fleet["dp4"].throughput
            / fleet["single"].throughput,
            "pp4_speedup": fleet["pp4"].throughput
            / fleet["single"].throughput,
            "ge_3x_dp4": fleet["dp4"].throughput
            >= 3.0 * fleet["single"].throughput,
            "batch": BATCH, "n_requests": N_REQ}

        # scheduler rows (PR 7): a skewed trace — arrivals at ~0.8x the
        # dp2 modeled capacity, every 17th request a 4x-cost straggler —
        # served by gang rounds vs the continuous-batching scheduler.
        # Gang rounds stall whole super-batches behind each straggler;
        # per-slot retirement + work stealing must not.
        rng = np.random.default_rng(7)
        rate = 0.8 * 2 * BATCH / t_round
        t_arr = np.cumsum(rng.exponential(1.0 / rate, N_REQ))
        skew = [Request(rid=i, image=np.zeros((1, 1, 1), np.float32),
                        t_arrival=float(t_arr[i]),
                        cost=4.0 if i % 17 == 16 else 1.0)
                for i in range(N_REQ)]

        def sched_sim(trace=None, metrics=None, **kw):
            eng = ServeEngine(cfg, [], batch=BATCH, replicas=2,
                              clock="modeled", execute=False, retries=2,
                              **kw)
            done, rep = eng.serve(list(skew), trace=trace,
                                  metrics=metrics)
            assert sorted(c.rid for c in done) == list(range(N_REQ))
            return rep

        grep = sched_sim()
        crep2 = sched_sim(scheduler="continuous", steal_threshold=1)
        for mode, rep in (("gang_skew", grep), ("cb", crep2)):
            rows[f"{name}_fleet_{mode}_model"] = {
                "us_per_call": 1e6 / rep.throughput,
                "fleet": {"mode": rep.mode, "replicas": 2, "pp_stages": 1,
                          "batch": BATCH, "scheduler": rep.scheduler,
                          "throughput_img_s": rep.throughput,
                          "p95_ms": rep.p95_ms,
                          "n_steals": rep.n_steals}}
        # observability row (PR 8): the SAME cb simulation re-run with a
        # TraceRecorder + MetricsRegistry attached. Instrumentation must
        # never advance the modeled clock, so tracing overhead on modeled
        # rows is exactly ZERO: the traced report — and therefore this
        # row's us_per_call — is byte-identical to the untraced cb row
        # (``traced_identical``, enforced by main() like the other
        # invariants)
        from repro.obs import MetricsRegistry, TraceRecorder
        t_rec, m_reg = TraceRecorder(), MetricsRegistry()
        trep = sched_sim(scheduler="continuous", steal_threshold=1,
                         trace=t_rec, metrics=m_reg)
        rows[f"{name}_fleet_cb_traced_model"] = {
            "us_per_call": 1e6 / trep.throughput,
            "fleet": {"mode": trep.mode, "replicas": 2, "pp_stages": 1,
                      "batch": BATCH, "scheduler": trep.scheduler,
                      "throughput_img_s": trep.throughput,
                      "p95_ms": trep.p95_ms,
                      "trace_events": len(t_rec),
                      "traced_identical":
                          trep.to_dict() == crep2.to_dict()}}
        rows[f"cb_vs_gang({name})"] = {
            "gang_p95_ms": grep.p95_ms, "cb_p95_ms": crep2.p95_ms,
            "p95_speedup": grep.p95_ms / crep2.p95_ms,
            "gang_img_s": grep.throughput, "cb_img_s": crep2.throughput,
            "n_steals": crep2.n_steals,
            "cb_beats_gang_p95": crep2.p95_ms < grep.p95_ms,
            "batch": BATCH, "n_requests": N_REQ}
    return rows


def compile_bench(fast: bool) -> dict:
    """Compile-phase trajectory rows (PR 5: the compile-once API).

    The compile phase (``repro.pipeline.compile_cnn``) is now a real
    pipeline stage with its own regression surface, so it gets its own
    rows next to the model rows:

    * ``{arch}_compile_cold`` / ``{arch}_compile_warm`` — measured
      wall-time of a cold compile (full DSE sweep) and a warm recompile
      (registry hits only), with the sweep/hit counters attached.
      Wall-clock rows are NOT gated (CI machines are noisy).
    * ``{arch}_compile_sweeps_model`` — the DETERMINISTIC compile cost:
      how many DSE sweeps a cold compile runs (``us_per_call`` carries
      the count; see ``unit``). Under the perf gate: a PR that makes the
      compile path sweep >10% more shapes fails, exactly like a modelled
      layer-time regression.
    * the warm row also records ``sweep_free`` — a warm recompile (and
      therefore a compile from a committed ``save_plan`` table) must do
      ZERO sweeps; main() enforces it like the int8/fleet invariants.
    """
    from repro.configs import get_config
    from repro.kernels import autotune
    from repro.models.cnn import init_cnn_params
    from repro.pipeline import ExecutionSpec, Serving, compile_cnn

    import jax

    rows: dict = {}
    for name in ("alexnet",) if fast else ("alexnet", "vgg16"):
        cfg = get_config(name)
        spec = ExecutionSpec(serving=Serving(batch=8, clock="modeled"))
        params = init_cnn_params(jax.random.key(0), cfg)  # not timed

        autotune.clear_registry()
        autotune.reset_sweep_stats()
        t0 = time.perf_counter()
        compiled = compile_cnn(cfg, spec, params)
        cold_us = (time.perf_counter() - t0) * 1e6
        cold = autotune.sweep_stats()
        n_sweeps = cold["conv_sweeps"] + cold["gemm_sweeps"]

        autotune.reset_sweep_stats()
        t0 = time.perf_counter()
        compile_cnn(cfg, spec, params)
        warm_us = (time.perf_counter() - t0) * 1e6
        warm = autotune.sweep_stats()

        plan_rows = compiled.plan_table.summary()
        rows[f"{name}_compile_cold"] = {
            "us_per_call": cold_us, "compile": dict(cold, **plan_rows)}
        rows[f"{name}_compile_warm"] = {
            "us_per_call": warm_us, "compile": dict(warm),
            "sweep_free": warm["conv_sweeps"] + warm["gemm_sweeps"] == 0}
        rows[f"{name}_compile_sweeps_model"] = {
            "us_per_call": float(n_sweeps),
            "unit": "dse_sweeps (deterministic count, not wall time)",
            "compile": dict(cold, **plan_rows)}
    return rows


def drift_bench(fast: bool) -> dict:
    """Measured-plan drift rows (PR 9): close the model->hardware loop.

    One measured cold compile of the AlexNet smoke config (interpret-mode
    Pallas, cheap trimmed-mean harness) exercises the whole loop:
    profiler -> format-3 plan table -> drift report. Wall clock and
    interpret-mode ratios quantify the HARNESS, not the TPU (the backend
    fingerprint recorded in the row says which), so neither row is
    numerically gated — but three boolean invariants ARE enforced by
    main() on every run, like the int8/fleet ones:

    * ``drift_provenance_ok`` — every plan got a measurement, the table
      carries the backend fingerprint, and the drift report reconciles
      exactly with the table (``validate_drift`` returns no errors);
    * ``seeded_measure_free`` — recompiling FROM the measured table runs
      ZERO measurements even with ``measure=True`` (the measured table
      is an artifact, not a trigger);
    * ``seeded_byte_identical`` — and reproduces the table byte-for-byte
      (measurements inherit verbatim through save/load/recompile).
    """
    from repro.configs import get_config
    from repro.kernels import autotune
    from repro.obs import MeasureOptions, drift_report, validate_drift
    from repro.obs.profiler import clear_measure_cache
    from repro.pipeline import ExecutionSpec, Serving, compile_cnn

    rows: dict = {}
    cfg = get_config("alexnet").smoke()
    spec = ExecutionSpec(serving=Serving(batch=2, clock="modeled"))
    opts = MeasureOptions(warmup=1, iters=1, repeats=1 if fast else 3,
                          trim=0 if fast else 1, interpret=True)

    autotune.clear_registry()
    autotune.reset_measure_stats()
    clear_measure_cache()
    t0 = time.perf_counter()
    compiled = compile_cnn(cfg, spec, with_engine=False, measure=True,
                           measure_opts=opts)
    cold_us = (time.perf_counter() - t0) * 1e6
    cold_stats = autotune.measure_stats()
    table = compiled.plan_table
    report = drift_report(table)
    errors = validate_drift(report, table=json.loads(table.to_json()))

    autotune.reset_measure_stats()
    warm = compile_cnn(cfg, spec, plans=table, with_engine=False,
                       measure=True, measure_opts=opts)
    warm_stats = autotune.measure_stats()

    stats = report.get("ratio") or {}
    meas = report.get("measurement") or {}
    rows["alexnet_smoke_measured_compile"] = {
        "us_per_call": cold_us,
        "drift": dict(cold_stats, n_plans=report["n_plans"],
                      n_measured=report["n_measured"])}
    rows["measured_vs_modeled(alexnet)"] = {
        "n_plans": report["n_plans"],
        "n_measured": report["n_measured"],
        "backend": meas.get("backend"),
        "harness": meas.get("harness"),
        "ratio_geomean": stats.get("geomean"),
        "ratio_min": stats.get("min"),
        "ratio_max": stats.get("max"),
        "drift_provenance_ok": (
            not errors
            and report["n_plans"] > 0
            and report["n_measured"] == report["n_plans"]
            and meas.get("backend") is not None),
        "seeded_measure_free":
            sum(warm_stats.values()) == 0,
        "seeded_byte_identical":
            warm.plan_table.to_json() == table.to_json()}
    return rows


def analysis_bench(fast: bool) -> dict:
    """Static-analysis trajectory row (PR 10): the pre-flight's own cost.

    One ``analysis_wall`` row records the wall time of a full
    static-verification pass — the determinism/contract lint over
    ``src/repro`` plus a Head-1 verification of the process plan
    registry snapshot. Wall clock, so the row is INFORMATIONAL and
    deliberately named outside the ``*_model`` perf gate; the attached
    counters (files scanned, findings) are what future PRs diff. The
    pass must stay pure: it is the one stage here that performs zero
    sweeps and zero measurements (asserted, like the other invariants).
    """
    from repro.analysis import run_lint, verify_plan_table
    from repro.kernels import autotune
    from repro.pipeline.plan_table import PlanTable

    sweep0, meas0 = autotune.sweep_stats(), autotune.measure_stats()
    t0 = time.perf_counter()
    lint_findings, n_files = run_lint("src/repro", repo_root=".")
    table = PlanTable.from_registry()
    verify_findings = verify_plan_table(table, path="registry")
    wall_us = (time.perf_counter() - t0) * 1e6
    assert autotune.sweep_stats() == sweep0, "static analysis swept"
    assert autotune.measure_stats() == meas0, "static analysis measured"
    return {"analysis_wall": {
        "us_per_call": wall_us,
        "analysis": {"files_scanned": n_files,
                     "lint_findings": len(lint_findings),
                     "verify_rows": len(table),
                     "verify_findings": len(verify_findings),
                     "pure": True}}}


def check_against(path: str, rows: dict, *, tol: float = 0.10) -> tuple:
    """Compare modelled layer rows against a committed trajectory.

    Returns ``(regressions, new_rows)``:
      * regressions — strings for any ``*_model`` row whose modelled
        roofline time grew more than ``tol`` vs the committed file
        (these FAIL the gate);
      * new_rows — ``*_model`` rows present in the fresh results but
        absent from (or malformed in) the committed baseline. These are
        INFORMATIONAL, not failures: a PR that adds a new modelled
        configuration (e.g. the int8 rows) must be able to land through
        the gate, and the rows become gated once committed.
    Non-model rows (measured wall clock, comparison summaries) are never
    gated.
    """
    with open(path) as f:
        committed = json.load(f)
    regressions, new_rows = [], []
    for name, row in rows.items():
        if not name.endswith("_model"):
            continue
        old = committed.get(name)
        if not isinstance(old, dict) or "us_per_call" not in old:
            new_rows.append(f"{name}: modelled {row['us_per_call']:.1f}us "
                            f"(no committed baseline)")
            continue
        was, now = old["us_per_call"], row["us_per_call"]
        if now > was * (1 + tol):
            regressions.append(
                f"{name}: modelled {now:.1f}us vs committed {was:.1f}us "
                f"(+{(now / was - 1) * 100:.1f}% > {tol * 100:.0f}%)")
    return regressions, new_rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="skip the slow full-scale VGG timing")
    ap.add_argument("--check-against", metavar="PATH", default=None,
                    help="perf-regression gate: fail if any modelled layer "
                         "time regressed >10%% vs the trajectory at PATH")
    args, _ = ap.parse_known_args()

    sys.path.insert(0, "src")
    from benchmarks import (bandwidth, fig7_dse, fig8_timeline,
                            lm_roofline, lrn_accuracy, table1_comparison)
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()

    csv_rows = []

    def run(name, fn):
        t0 = time.perf_counter()
        fn()
        csv_rows.append((name, (time.perf_counter() - t0) * 1e6))

    run("lrn_accuracy(paper_0.5pct_claim)", lrn_accuracy.main)
    run("fig7_dse(vec_x_cu_x_ohblk_sweep)", fig7_dse.main)
    run("bandwidth(fusion_claim)", bandwidth.main)
    if not args.fast:
        run("table1(alexnet_vgg_throughput)", table1_comparison.main)
        run("fig8_timeline(stage_profile)", fig8_timeline.main)
    run("lm_roofline(assigned_archs)", lm_roofline.main)

    conv_rows = conv_bench(args.fast)
    conv_rows.update(fleet_bench(args.fast))
    # LAST TWO: compile_bench and drift_bench clear the plan registry
    # to time/measure cold compiles
    conv_rows.update(compile_bench(args.fast))
    conv_rows.update(drift_bench(args.fast))
    # LAST: the static-analysis pass verifies the registry the two cold
    # compiles above just populated (informational wall-clock row,
    # outside the *_model gate by construction)
    conv_rows.update(analysis_bench(args.fast))
    # the int8 acceptance invariant is deterministic (pure cost model),
    # so it is enforced on EVERY run, gate or not: int8 must model
    # <= 0.5x fp32 on every bandwidth-bound conv layer
    violations = [
        f"{name}: int8 modelled > 0.5x fp32 on a bandwidth-bound layer: "
        + ", ".join(f"{l['layer']} ratio {l['ratio']:.3f}"
                    for l in row["layers"]
                    if l["bandwidth_bound_fp32"] and l["ratio"] > 0.5)
        for name, row in conv_rows.items()
        if name.startswith("int8_vs_fp32(")
        and not row["int8_le_half_on_bandwidth_bound"]]
    # likewise the fleet acceptance (PR 4): 4 data-parallel replicas
    # must model >= 3x aggregate throughput vs one replica
    violations += [
        f"{name}: dp4 modelled only {row['dp4_speedup']:.2f}x the "
        f"single-replica throughput (acceptance: >= 3x)"
        for name, row in conv_rows.items()
        if name.startswith("fleet_vs_single(") and not row["ge_3x_dp4"]]
    # and the scheduler acceptance (PR 7): under the skewed trace,
    # continuous batching must beat the gang scheduler's p95 latency
    violations += [
        f"{name}: continuous batching p95 {row['cb_p95_ms']:.3f} ms did "
        f"not beat gang p95 {row['gang_p95_ms']:.3f} ms on the skewed "
        f"trace (acceptance: cb < gang)"
        for name, row in conv_rows.items()
        if name.startswith("cb_vs_gang(") and not row["cb_beats_gang_p95"]]
    # and the observability acceptance (PR 8): attaching a trace/metrics
    # recorder must not perturb the modeled run at all — the traced cb
    # report must equal the untraced one field-for-field
    violations += [
        f"{name}: tracing perturbed the modeled run (traced report != "
        f"untraced cb report; instrumentation must not touch the clock)"
        for name, row in conv_rows.items()
        if name.endswith("_fleet_cb_traced_model")
        and not row["fleet"]["traced_identical"]]
    # and the compile-once acceptance (PR 5): a warm recompile — and
    # therefore a compile seeded from a committed save_plan table —
    # must perform ZERO DSE sweeps
    violations += [
        f"{name}: warm recompile ran DSE sweeps ({row['compile']}); the "
        f"plan registry/table must make recompiles sweep-free"
        for name, row in conv_rows.items()
        if name.endswith("_compile_warm") and not row["sweep_free"]]
    # and the drift acceptance (PR 9): a measured compile must measure
    # every plan with full provenance (report reconciles with the
    # table), and a compile seeded from the measured table must run
    # zero measurements and reproduce it byte-for-byte
    for flag, why in (
            ("drift_provenance_ok",
             "drift report does not reconcile with the measured plan "
             "table (coverage/fingerprint/validate_drift)"),
            ("seeded_measure_free",
             "compile seeded from the measured table re-ran "
             "measurements (the table is an artifact, not a trigger)"),
            ("seeded_byte_identical",
             "compile seeded from the measured table did not reproduce "
             "it byte-for-byte")):
        violations += [
            f"{name}: {why}"
            for name, row in conv_rows.items()
            if name.startswith("measured_vs_modeled(") and not row[flag]]
    # gate BEFORE writing: the committed file is the baseline, and a
    # failing gate must NOT overwrite it (a rerun would then compare the
    # regressed values against themselves and pass)
    regressions, new_rows = (check_against(args.check_against, conv_rows)
                             if args.check_against else ([], []))
    if not regressions and not violations:
        with open(BENCH_JSON, "w") as f:
            json.dump(conv_rows, f, indent=1)
        print(f"\nwrote {BENCH_JSON} ({len(conv_rows)} rows)")

    print("\nname,us_per_call,derived")
    for name, us in csv_rows:
        print(f"{name},{us:.0f},ok")
    for name, row in conv_rows.items():
        if "us_per_call" in row:
            p = row.get("plan")
            if p and "c_blk" in p:
                derived = (f"plan=b{p.get('b_blk', 1)}xc{p['c_blk']}"
                           f"xm{p['m_blk']}xh{p['oh_blk']}")
            elif p and "bm" in p:
                derived = f"gemm=m{p['bm']}xn{p['bn']}xk{p['bk']}"
            elif row.get("fleet"):
                f = row["fleet"]
                derived = (f"fleet={f['mode']}xR{f['replicas']}"
                           f"xS{f['pp_stages']}")
            elif "compile" in row:
                c = row["compile"]
                derived = (f"compile=sweeps{c['conv_sweeps']}"
                           f"+{c['gemm_sweeps']}"
                           f"_hits{c['conv_hits']}+{c['gemm_hits']}")
            else:
                derived = "ref"
            print(f"{name},{row['us_per_call']:.0f},{derived}")

    if violations:
        print("\nINT8 ACCEPTANCE VIOLATION:")
        for v in violations:
            print(f"  {v}")
    if args.check_against:
        if new_rows:
            print(f"\nNEW rows vs {args.check_against} "
                  f"(informational, gated once committed):")
            for r in new_rows:
                print(f"  {r}")
        if regressions:
            print(f"\nPERF REGRESSION vs {args.check_against}:")
            for r in regressions:
                print(f"  {r}")
        if not regressions and not violations:
            print(f"\nperf gate vs {args.check_against}: OK "
                  f"(no modelled layer regressed >10%; "
                  f"{len(new_rows)} new rows)")
    if regressions or violations:
        sys.exit(1)


if __name__ == "__main__":
    main()
