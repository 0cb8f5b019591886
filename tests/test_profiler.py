"""Measured-plan profiler + drift observability tests (PR 9).

Covers the measurement harness (deterministic operands, trimmed mean,
backend-aware interpret default, cache + stats counters), the guided
top-K refinement, and — from ONE shared measured compile (a module
fixture: interpret-mode kernel timing is slow, so every whole-pipeline
assertion reads the same artifact) — format-3 coverage, the
seeded-compile contract (zero re-measurement, byte-identical table),
drift report/CLI reconciliation, registry metrics, compile-track trace
spans, and the measured roofline-breakdown columns.
"""
import json

import pytest

from repro.configs import get_config
from repro.kernels import autotune, ops
from repro.obs import (MeasureOptions, TraceRecorder, backend_fingerprint,
                       drift_report, record_drift, refine_plan, shortlist,
                       validate_drift)
from repro.obs.drift import format_drift
from repro.obs.drift import main as drift_main
from repro.obs.metrics import MetricsRegistry
from repro.obs.profiler import (clear_measure_cache, measure_record,
                                trimmed_mean)
from repro.pipeline import ExecutionSpec, Serving, compile_cnn, load_plan
from repro.pipeline.plan_table import plan_key

# a deliberately tiny layer: interpret-mode measurements on it cost
# milliseconds, so the harness unit tests stay cheap
SMALL = autotune.ConvShape(h=8, w=8, c=8, kh=3, kw=3, m=16, pad=1)
SMALL_GEMM = autotune.GemmShape(m=4, k=32, n=32)
CHEAP = MeasureOptions(warmup=1, iters=1, repeats=2, trim=0,
                       interpret=True)


# ---------------------------------------------------------------------------
# harness units: trimmed mean, deterministic seeds, interpret resolution
# ---------------------------------------------------------------------------

def test_trimmed_mean_drops_outliers():
    assert trimmed_mean([1.0, 2.0, 3.0, 100.0], trim=1) == 2.5
    # too few samples to trim: kept whole
    assert trimmed_mean([1.0, 100.0], trim=1) == 50.5
    assert trimmed_mean([4.0], trim=0) == 4.0


def test_measure_seed_deterministic_per_point():
    """The operand PRNG seed is a pure function of (shape, plan) —
    re-measuring a point benchmarks identical bytes (crc32, not the
    per-process-salted hash())."""
    p1 = autotune.get_plan(SMALL)
    p2 = autotune.ConvPlan(c_blk=8, m_blk=8, oh_blk=2)
    s = autotune._measure_seed(SMALL, p1)
    assert s == autotune._measure_seed(SMALL, p1)
    assert s != autotune._measure_seed(SMALL, p2)
    assert s != autotune._measure_seed(SMALL_GEMM, p1)


def test_measure_plan_counts_and_positive():
    autotune.reset_measure_stats()
    p = autotune.get_plan(SMALL)
    t = autotune.measure_plan(SMALL, p, iters=1, warmup=1, interpret=True)
    assert t > 0.0
    st = autotune.measure_stats()
    assert st["conv_measured"] == 1 and st["gemm_measured"] == 0


@pytest.mark.parametrize("dtype", ["float32", "int8"])
def test_measure_gemm_plan_both_dtypes(dtype):
    """The GEMM harness measures the kernel the plan was tuned for —
    int8 goes through the fixed-point path (quantized operands +
    requantize scale), not a float stand-in."""
    import dataclasses
    shape = dataclasses.replace(SMALL_GEMM, dtype=dtype)
    plan = autotune.get_gemm_plan(shape)
    autotune.reset_measure_stats()
    t = autotune.measure_gemm_plan(shape, plan, iters=1, warmup=1,
                                   interpret=True)
    assert t > 0.0
    assert autotune.measure_stats()["gemm_measured"] == 1


def test_interpret_default_is_backend_aware():
    """interpret=None resolves from the backend-chosen mode — measuring
    the interpreter while the pipeline runs compiled (or vice versa)
    would be silently meaningless."""
    from repro.kernels.mode import resolve_interpret
    with ops.interpret_mode(True):
        assert resolve_interpret(None) is True
        assert MeasureOptions().resolve_interpret() is True
        assert backend_fingerprint()["interpret"] is True
    # explicit values win regardless of process mode
    with ops.interpret_mode(True):
        assert resolve_interpret(False) is False
    fp = backend_fingerprint(True)
    assert {"platform", "device", "jax", "interpret", "timer"} <= set(fp)
    assert fp["peaks"] is None          # interpreted: no device roofline


def test_compiled_fingerprint_refuses_unknown_device_kind():
    """A compiled measurement is priced against its device's published
    peaks; a device kind missing from the table is an error."""
    from repro.core.roofline import DEVICE_PEAKS, TARGET_DEVICE_KIND
    assert TARGET_DEVICE_KIND == "TPU v5 lite"
    assert DEVICE_PEAKS[TARGET_DEVICE_KIND]["bf16_flops"] == 197e12
    with pytest.raises(KeyError, match="no published peaks"):
        backend_fingerprint(False)     # this backend's kind is not a TPU


def test_measure_record_cache_counts_hits():
    clear_measure_cache()
    autotune.reset_measure_stats()
    p = autotune.get_plan(SMALL)
    r1 = measure_record("conv", SMALL, p, opts=CHEAP)
    st = autotune.measure_stats()
    assert st["conv_measured"] == CHEAP.repeats
    assert st["conv_measure_hits"] == 0
    r2 = measure_record("conv", SMALL, p, opts=CHEAP)
    st = autotune.measure_stats()
    assert r2 == r1                              # memoised record
    assert st["conv_measured"] == CHEAP.repeats  # no new timing
    assert st["conv_measure_hits"] == 1
    # a different harness is a different measurement point
    other = MeasureOptions(warmup=1, iters=2, repeats=1, trim=0,
                           interpret=True)
    measure_record("conv", SMALL, p, opts=other)
    assert autotune.measure_stats()["conv_measure_hits"] == 1
    # the record fixes units at measure time: t_model_call is per call
    assert r1["t_model_call"] == pytest.approx(p.t_model * SMALL.b)
    assert r1["interpret"] is True
    assert {"warmup", "iters", "repeats", "trim"} <= set(r1)


# ---------------------------------------------------------------------------
# guided refinement: shortlist + top-K measurement, never exhaustive
# ---------------------------------------------------------------------------

def test_shortlist_is_modeled_top_k():
    all_plans = autotune.enumerate_plans(SMALL, 16 * 2 ** 20)
    best_t = min(p.t_model for p in all_plans)
    top = shortlist(SMALL, 3)
    assert len(top) == 3
    assert top[0].t_model == best_t
    assert [p.t_model for p in top] == sorted(p.t_model for p in top)[:3]
    # gemm shapes route to the gemm enumerator
    gtop = shortlist(autotune.GemmShape(m=8, k=256, n=128), 2)
    assert len(gtop) == 2 and hasattr(gtop[0], "bm")


def test_refine_plan_measures_exactly_top_k():
    clear_measure_cache()
    autotune.reset_measure_stats()
    opts = MeasureOptions(warmup=1, iters=1, repeats=1, trim=0,
                          interpret=True)
    best, records = refine_plan(SMALL, top_k=2, opts=opts)
    assert len(records) == 2                     # guided, not exhaustive
    assert autotune.measure_stats()["conv_measured"] == 2
    assert records[0]["model_pick"] and not records[1]["model_pick"]
    assert [r["rank_model"] for r in records] == [0, 1]
    win = min(records, key=lambda r: r["t_measured"])
    assert best.to_dict() == win["plan"]


# ---------------------------------------------------------------------------
# the whole-pipeline loop: ONE shared measured compile
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def measured_compile():
    """One measured cold compile of alexnet smoke (interpret mode,
    cheapest harness) + the trace it emitted — shared by every
    whole-pipeline test below."""
    cfg = get_config("alexnet").smoke()
    spec = ExecutionSpec(serving=Serving(batch=2, clock="modeled"))
    opts = MeasureOptions(warmup=1, iters=1, repeats=1, trim=0,
                          interpret=True)
    autotune.clear_registry()
    autotune.reset_measure_stats()
    clear_measure_cache()
    trace = TraceRecorder()
    compiled = compile_cnn(cfg, spec, with_engine=False, measure=True,
                           measure_opts=opts, trace=trace)
    stats = autotune.measure_stats()
    return cfg, spec, opts, compiled, trace, stats


def test_measured_compile_covers_every_plan(measured_compile):
    _, _, _, compiled, _, stats = measured_compile
    table = compiled.plan_table
    doc = json.loads(table.to_json())
    assert doc["format"] == 3
    n_plans = len(table)
    assert n_plans > 0
    assert len(table.measurements()) == n_plans          # EVERY plan
    assert table.summary()["measured_plans"] == n_plans
    assert stats["conv_measured"] == len(table.conv)
    assert stats["gemm_measured"] == len(table.gemm)
    meas = table.provenance["measurement"]
    assert meas["backend"]["interpret"] is True
    assert meas["harness"] == {"warmup": 1, "iters": 1, "repeats": 1,
                               "trim": 0}
    assert meas["measure_stats"]["conv_measured"] == len(table.conv)


def test_seeded_compile_inherits_measurements_verbatim(measured_compile):
    """The acceptance contract: a compile seeded from the measured
    table runs ZERO measurements even with measure=True, and reproduces
    the table byte-for-byte."""
    cfg, spec, opts, compiled, _, _ = measured_compile
    autotune.reset_measure_stats()
    warm = compile_cnn(cfg, spec, plans=compiled.plan_table,
                       with_engine=False, measure=True, measure_opts=opts)
    assert sum(autotune.measure_stats().values()) == 0
    assert warm.plan_table.to_json() == compiled.plan_table.to_json()


def test_measured_table_save_load_save_byte_identical(
        measured_compile, tmp_path):
    _, _, _, compiled, _, _ = measured_compile
    path = str(tmp_path / "plan_table.json")
    compiled.save_plan(path)
    assert load_plan(path).to_json() == compiled.plan_table.to_json()


def test_compile_trace_has_sweep_and_measure_spans(measured_compile):
    _, _, _, compiled, trace, _ = measured_compile
    events = json.loads(trace.to_json())["traceEvents"]
    spans = [e for e in events if e.get("ph") == "X"]
    sweeps = [e for e in spans if e["name"] == "sweep"]
    measures = [e for e in spans if e["name"] == "measure"]
    assert len(sweeps) == 1
    assert len(measures) == len(compiled.plan_table)
    for e in measures:
        assert e["args"]["t_measured"] > 0
        assert e["args"]["kind"] in ("conv", "gemm")
        assert e["dur"] > 0


def test_breakdown_gains_measured_columns(measured_compile):
    _, _, _, compiled, _, _ = measured_compile
    rows = compiled.roofline_breakdown()
    assert rows
    for row in rows:
        assert row["t_measured"] is not None and row["t_measured"] > 0
        assert row["drift"] is not None and row["drift"] > 0
    json.dumps(rows)                 # still JSON-serialisable


def test_drift_report_reconciles_with_table(measured_compile):
    _, _, _, compiled, _, _ = measured_compile
    table = compiled.plan_table
    report = drift_report(table)
    assert report["n_plans"] == len(table)
    assert report["n_measured"] == len(table)
    assert report["n_unmeasured"] == 0
    assert validate_drift(report, table=json.loads(table.to_json())) == []
    stats = report["ratio"]
    assert stats and stats["n"] == len(table)
    assert stats["min"] <= stats["geomean"] <= stats["max"]
    for row in report["rows"]:
        assert row["ratio"] == pytest.approx(
            row["t_measured"] / row["t_model_call"])
    # the human-readable view renders every row
    text = format_drift(report)
    assert f"plans: {len(table)}" in text and "ratio" in text


def test_drift_report_on_unmeasured_table(measured_compile):
    """An unmeasured (format <= 2 equivalent) table still reports: one
    row per plan, everything unmeasured, no ratio stats."""
    _, _, _, compiled, _, _ = measured_compile
    doc = json.loads(compiled.plan_table.to_json())
    for kind in ("conv", "gemm"):
        for r in doc[kind]:
            r.pop("measured", None)
    doc["provenance"].pop("measurement", None)
    report = drift_report(doc)
    assert report["n_measured"] == 0
    assert report["n_unmeasured"] == report["n_plans"] > 0
    assert report["ratio"] is None and report["measurement"] is None
    assert all(r["t_measured"] is None for r in report["rows"])
    assert validate_drift(report, table=doc) == []


def test_record_drift_feeds_registry(measured_compile):
    _, _, _, compiled, _, _ = measured_compile
    report = drift_report(compiled.plan_table)
    reg = MetricsRegistry()
    record_drift(reg, report)
    snap = json.loads(reg.to_json())
    assert snap["gauges"]["drift_plans_total"] == report["n_plans"]
    assert snap["gauges"]["drift_plans_measured"] == report["n_measured"]
    assert snap["gauges"]["drift_ratio_geomean"] == pytest.approx(
        report["ratio"]["geomean"])
    hist = snap["histograms"]["plan_drift_ratio"]
    assert hist["count"] == report["n_measured"]
    prom = reg.to_prometheus()
    assert "plan_drift_ratio_bucket" in prom
    assert "drift_ratio_geomean" in prom


def test_drift_cli_roundtrip(measured_compile, tmp_path, capsys):
    _, _, _, compiled, _, _ = measured_compile
    table_path = str(tmp_path / "plan_table.json")
    compiled.save_plan(table_path)
    out_json = str(tmp_path / "drift.json")
    out_prom = str(tmp_path / "drift.prom")
    rc = drift_main([table_path, "--json", out_json,
                     "--metrics", out_prom])
    assert rc == 0
    assert "[obs.drift] OK" in capsys.readouterr().out
    report = json.loads(open(out_json).read())
    assert report["n_measured"] == len(compiled.plan_table)
    assert "plan_drift_ratio_bucket" in open(out_prom).read()
    # the CLI's report matches the library's, byte for byte
    lib = json.loads(json.dumps(drift_report(compiled.plan_table),
                                sort_keys=True))
    assert report == lib


def test_plan_key_joins_table_breakdown_and_drift(measured_compile):
    """plan_key is the ONE join key: every measured record found via the
    table maps onto a breakdown row's measured column."""
    _, _, _, compiled, _, _ = measured_compile
    table = compiled.plan_table
    by_key = table.measurements()
    assert len(by_key) == len(table)
    for row in (*table.conv, *table.gemm):
        assert plan_key(row) in by_key
        assert by_key[plan_key(row)]["t_measured"] \
            == row["measured"]["t_measured"]
