"""Observability for the serving AND compile stacks.

Five modules; trace/metrics/validate have no dependencies on the rest
of ``repro`` (the serve loops import *us*), while profiler/drift reach
into the kernel layer lazily (only when a measurement actually runs):

  * :mod:`repro.obs.trace` — :class:`TraceRecorder`, Chrome trace-event
    JSON export (Perfetto-viewable), byte-deterministic on the modeled
    clock; since PR 9 it also carries compile-phase ``sweep``/
    ``measure`` spans on the ``compile`` track; and :data:`SPANS`, the
    process's :class:`~repro.obs.trace.SpanLog` of wall-clock spans of
    real forwards (``cnn.*``) and garbage collections (``py.gc``), on
    by default (``REPRO_SPANS=0`` or :func:`set_spans` switch it off);
  * :mod:`repro.obs.metrics` — :class:`MetricsRegistry` of counters /
    gauges / histograms / windows, JSON + Prometheus text exports;
  * :mod:`repro.obs.profiler` — the measured-refinement harness
    (deterministic warmup/iters/trimmed-mean timing over
    ``autotune.measure_plan`` / ``measure_gemm_plan``, backend
    fingerprints, the guided top-K :func:`~repro.obs.profiler.refine_plan`
    pass, and :func:`~repro.obs.profiler.profile_table` behind
    ``compile_cnn(measure=True)``);
  * :mod:`repro.obs.drift` — measured-vs-modeled drift reports over a
    format-3 plan table, drift gauges + ratio histogram for the
    registry (also a CLI: ``python -m repro.obs.drift``);
  * :mod:`repro.obs.validate` — schema validation, trace ↔ metrics ↔
    ``FleetReport`` reconciliation, and drift ↔ plan-table
    reconciliation (also a CLI: ``python -m repro.obs.validate``).
"""
from .trace import (  # noqa: F401
    CAT_COMPILE,
    CAT_FLEET,
    CAT_REQUEST,
    CAT_ROUND,
    CAT_WALL,
    COMPILE_TRACK,
    FLEET_TRACK,
    SPANS,
    WALL_TRACK,
    SpanLog,
    TraceRecorder,
    now_ns,
    set_spans,
)
from .metrics import (  # noqa: F401
    DEFAULT_LATENCY_BUCKETS,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    WindowSeries,
    record_report,
)
from .profiler import (  # noqa: F401
    MeasureOptions,
    backend_fingerprint,
    clear_measure_cache,
    measure_record,
    profile_table,
    refine_plan,
    shortlist,
)
from .drift import (  # noqa: F401
    DRIFT_RATIO_BUCKETS,
    drift_report,
    record_drift,
)
from .validate import (  # noqa: F401
    reconcile,
    validate_analysis,
    validate_drift,
    validate_metrics,
    validate_trace,
)

__all__ = [
    "TraceRecorder",
    "CAT_REQUEST",
    "CAT_ROUND",
    "CAT_FLEET",
    "CAT_COMPILE",
    "FLEET_TRACK",
    "COMPILE_TRACK",
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
]
