"""Public-API surface snapshot (CI contract).

The compile-once refactor turned ``repro.pipeline`` and
``repro.kernels.ops`` into the two entry-point modules everything else
(examples, CLI, benchmarks, downstream users) imports from. These tests
pin their exported names so a future refactor cannot silently drop or
rename an entry point — changing the surface requires editing the
snapshot here, which is exactly the review trigger we want.
"""
import inspect

import repro.kernels.ops as ops
import repro.pipeline as pipeline

PIPELINE_SURFACE = {
    "AutoscalePolicy",
    "CompiledCNN",
    "ExecutionSpec",
    "Placement",
    "PlanTable",
    "Precision",
    "Serving",
    "SpecError",
    "Tiling",
    "compile_cnn",
    "load_artifact",
    "load_plan",
    "resolve_config",
    "save_artifact",
    "spec_from_config",
}

OBS_SURFACE = {
    "TraceRecorder",
    "CAT_REQUEST",
    "CAT_ROUND",
    "CAT_FLEET",
    "CAT_COMPILE",
    "FLEET_TRACK",
    "COMPILE_TRACK",
    # wall-clock spans of real forwards and GC pauses
    "CAT_WALL",
    "WALL_TRACK",
    "SpanLog",
    "SPANS",
    "set_spans",
    "now_ns",
    "MetricsRegistry",
    "Counter",
    "Gauge",
    "Histogram",
    "WindowSeries",
    "DEFAULT_LATENCY_BUCKETS",
    "record_report",
    # the measured-refinement profiler + drift loop (PR 9)
    "MeasureOptions",
    "backend_fingerprint",
    "clear_measure_cache",
    "measure_record",
    "profile_table",
    "refine_plan",
    "shortlist",
    "DRIFT_RATIO_BUCKETS",
    "drift_report",
    "record_drift",
    "validate_trace",
    "validate_metrics",
    "validate_drift",
    "validate_analysis",
    "reconcile",
}

# repro.kernels.autotune grew a declared surface with the measured-
# refinement hooks; the DSE/measure/registry entry points the profiler,
# plan table and benchmarks build on are pinned here.
AUTOTUNE_SURFACE = {
    "ConvShape",
    "ConvPlan",
    "GemmShape",
    "GemmPlan",
    "conv_vmem_bytes",
    "plan_fits",
    "score_plan",
    "enumerate_plans",
    "best_plan",
    "gemm_vmem_bytes",
    "gemm_plan_fits",
    "score_gemm_plan",
    "enumerate_gemm_plans",
    "best_gemm_plan",
    "measure_plan",
    "measure_gemm_plan",
    "get_plan",
    "get_gemm_plan",
    "plan_for_layer",
    "gemm_plan_for_layer",
    "clear_registry",
    "registry_snapshot",
    "gemm_registry_snapshot",
    "dump_registry",
    "seed_registry",
    "record_lookups",
    "sweep_stats",
    "reset_sweep_stats",
    "measure_stats",
    "reset_measure_stats",
    "count_measure_hit",
}

OPS_SURFACE = {
    "attention",
    "fc",
    "fused_conv",
    "get_interpret",
    "interpret_mode",
    "lrn",
}


def test_pipeline_exports_exactly_the_contract():
    assert set(pipeline.__all__) == PIPELINE_SURFACE
    for name in PIPELINE_SURFACE:
        assert hasattr(pipeline, name), f"repro.pipeline.{name} missing"


def test_ops_exports_exactly_the_contract():
    assert set(ops.__all__) == OPS_SURFACE
    for name in OPS_SURFACE:
        assert hasattr(ops, name), f"repro.kernels.ops.{name} missing"


def test_obs_exports_exactly_the_contract():
    import repro.obs as obs
    assert set(obs.__all__) == OBS_SURFACE
    for name in OBS_SURFACE:
        assert hasattr(obs, name), f"repro.obs.{name} missing"


def test_compiled_cnn_runtime_surface():
    """The CompiledCNN method contract of the compile-once API."""
    for method in ("forward", "forward_stage", "serve", "plans",
                   "save_plan", "load_plan", "save", "load",
                   "roofline_breakdown", "verify"):
        assert callable(getattr(pipeline.CompiledCNN, method, None)), \
            f"CompiledCNN.{method} missing"


def test_autotune_exports_exactly_the_contract():
    import repro.kernels.autotune as autotune
    assert set(autotune.__all__) == AUTOTUNE_SURFACE
    for name in AUTOTUNE_SURFACE:
        assert hasattr(autotune, name), \
            f"repro.kernels.autotune.{name} missing"


def test_compile_cnn_signature_stable():
    """The compile entry point's keyword surface (shims + CLI rely on
    these exact names)."""
    sig = inspect.signature(pipeline.compile_cnn)
    assert list(sig.parameters) == [
        "cfg", "spec", "params_or_calib", "plans", "plan_path", "key",
        "with_engine", "measure", "measure_opts", "trace"]


def test_execution_spec_subspec_fields():
    """The four sub-specs carve up the knob space exactly once."""
    import dataclasses
    assert sorted(f.name for f in dataclasses.fields(pipeline.Precision)) \
        == ["calib", "dtype", "quant"]
    assert sorted(f.name for f in dataclasses.fields(pipeline.Tiling)) \
        == ["autotune", "b_blk", "cu_num", "oh_blk", "vec_size",
            "vmem_budget"]
    assert sorted(f.name for f in dataclasses.fields(pipeline.Placement)) \
        == ["microbatches", "pp_stages", "replicas"]
    assert sorted(f.name for f in dataclasses.fields(pipeline.Serving)) \
        == ["autoscale", "backoff", "batch", "clock", "execute",
            "max_queue", "retries", "scheduler", "slo",
            "steal_threshold"]
    assert sorted(f.name for f in
                  dataclasses.fields(pipeline.AutoscalePolicy)) \
        == ["cooldown", "interval", "max_replicas", "min_replicas",
            "util_high", "util_low", "window"]
    assert sorted(f.name for f in
                  dataclasses.fields(pipeline.ExecutionSpec)) \
        == ["interpret", "placement", "precision", "serving", "tiling",
            "use_pallas"]
