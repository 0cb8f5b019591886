"""Kernel-profiling harness: measured refinement of modeled plans.

The paper's Fig.-7 DSE — and our ``repro.kernels.autotune`` sweep —
ranks hardware configurations by an analytic model alone. This module
closes the model→hardware loop (the ROADMAP's first open item): it
times plans with a deterministic warmup/iters/trimmed-mean harness
built on ``autotune.measure_plan`` / ``measure_gemm_plan``, stamps each
number with the backend fingerprint it was taken on, and writes the
results into the plan table (format 3) where the drift report
(:mod:`repro.obs.drift`) can price measured-vs-modeled per plan.

Two entry points:

  * :func:`refine_plan` — guided search over ONE layer shape: shortlist
    the ``top_k`` modeled candidates and measure only those (the
    ``launch/hillclimb.py`` discipline — the model proposes, the
    stopwatch disposes; never the exhaustive enumeration).
  * :func:`profile_table` — measure every plan a compile resolved and
    return the format-3 table with ``t_measured`` + measurement
    provenance attached. ``compile_cnn(measure=True)`` calls this.

Measurements are memoised per ``(kind, shape, plan, interpret,
harness)`` in a process-wide cache, so a warm recompile over the same
spec re-times nothing — cache hits are counted in
``autotune.measure_stats`` (``*_measure_hits``), mirroring the DSE
``sweep_stats`` economy. A compile SEEDED from a measured table never
reaches this module at all: it inherits the seed's measurements
verbatim (``PlanTable.with_measurements``), keeping artifact
save→load→save byte-identical.

Kernel modules are imported lazily: ``repro.obs`` stays importable
without pulling jax until a measurement actually runs.
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

__all__ = [
    "MeasureOptions",
    "backend_fingerprint",
    "clear_measure_cache",
    "measure_record",
    "profile_table",
    "refine_plan",
    "shortlist",
    "trimmed_mean",
]


@dataclass(frozen=True)
class MeasureOptions:
    """The deterministic measurement protocol, as data.

    ``repeats`` independent timing samples are taken (each sample is
    one ``measure_plan`` call: ``warmup`` un-timed calls then ``iters``
    timed calls averaged), the ``trim`` fastest and slowest samples are
    dropped, and the rest are meaned — the standard guard against both
    one-off stalls and suspiciously-fast outliers. ``interpret=None``
    resolves to the process backend mode (``mode.get_interpret()``);
    the RESOLVED mode is recorded per measurement, because a number
    without its backend is noise.
    """
    warmup: int = 1
    iters: int = 3
    repeats: int = 5
    trim: int = 1
    top_k: int = 2              # refine_plan's modeled-shortlist size
    interpret: Optional[bool] = None

    def resolve_interpret(self) -> bool:
        from repro.kernels.mode import resolve_interpret
        return resolve_interpret(self.interpret)

    def harness(self) -> dict:
        """The JSON view stored in measurement provenance."""
        return {"warmup": self.warmup, "iters": self.iters,
                "repeats": self.repeats, "trim": self.trim}


def backend_fingerprint(interpret: Optional[bool] = None) -> dict:
    """Where a measurement was taken: platform, device kind, jax
    version, the RESOLVED interpret mode and, for compiled kernels, the
    device's published peaks. Stored in
    ``provenance["measurement"]["backend"]`` — an interpret-mode number
    compared against a real-TPU number is not drift, it is apples vs
    oranges, and this dict is how the drift report can tell. A compiled
    measurement on a device kind with no entry in
    ``core.roofline.DEVICE_PEAKS`` raises: its roofline would be priced
    against another chip."""
    import jax

    from repro.core.roofline import device_peaks
    from repro.kernels.mode import resolve_interpret
    interpret = resolve_interpret(interpret)
    dev = jax.devices()[0]
    kind = getattr(dev, "device_kind", str(dev))
    return {"platform": jax.default_backend(),
            "device": kind,
            "jax": jax.__version__,
            "interpret": interpret,
            "peaks": None if interpret else dict(device_peaks(kind)),
            "timer": "time.perf_counter"}


def trimmed_mean(samples: List[float], trim: int) -> float:
    """Mean after dropping the ``trim`` smallest and largest samples
    (kept whole when there are not enough samples to trim)."""
    vs = sorted(samples)
    if trim > 0 and len(vs) > 2 * trim:
        vs = vs[trim:-trim]
    return sum(vs) / len(vs)


# (kind, shape, plan, interpret, warmup, iters, repeats, trim) ->
# measured record. Process-wide on purpose: the registry-memoised DSE
# makes warm recompiles sweep-free, and this cache makes them
# measurement-free (counted via autotune.measure_stats).
_CACHE: Dict[tuple, dict] = {}


def clear_measure_cache() -> None:
    _CACHE.clear()


def measure_record(kind: str, shape, plan, *,
                   opts: Optional[MeasureOptions] = None) -> dict:
    """One plan's measured record: trimmed-mean wall-clock seconds/call.

    ``kind`` is ``"conv"`` or ``"gemm"``; ``shape``/``plan`` the
    autotune dataclasses. The record carries the measured time, the
    harness parameters and resolved interpret mode that produced it,
    and ``t_model_call`` — the modeled time in the SAME per-call unit
    (``ConvPlan.t_model`` is seconds/image, so it is scaled by
    ``shape.b``; ``GemmPlan.t_model`` is already per call), so drift
    ratios never mix units.
    """
    from repro.kernels import autotune

    opts = opts or MeasureOptions()
    interpret = opts.resolve_interpret()
    key = (kind, shape, plan, interpret,
           opts.warmup, opts.iters, opts.repeats, opts.trim)
    hit = _CACHE.get(key)
    if hit is not None:
        autotune.count_measure_hit(kind)
        return hit
    fn = (autotune.measure_plan if kind == "conv"
          else autotune.measure_gemm_plan)
    samples = [fn(shape, plan, iters=opts.iters, warmup=opts.warmup,
                  interpret=interpret)
               for _ in range(max(1, opts.repeats))]
    per_call = plan.t_model * (shape.b if kind == "conv" else 1)
    record = {"t_measured": trimmed_mean(samples, opts.trim),
              "t_model_call": per_call,
              "interpret": interpret,
              **opts.harness()}
    _CACHE[key] = record
    return record


def shortlist(shape, k: int, *, vmem_budget: Optional[int] = None) -> list:
    """The ``k`` best MODELED plans for a layer shape — the hypotheses
    the measured pass is allowed to spend a stopwatch on. Ties break
    toward larger tiles, like ``best_plan``."""
    from repro.core.roofline import VMEM_BYTES
    from repro.kernels import autotune

    budget = VMEM_BYTES if vmem_budget is None else vmem_budget
    if isinstance(shape, autotune.GemmShape):
        plans = autotune.enumerate_gemm_plans(shape, budget)
        vol = lambda p: p.bm * p.bn * p.bk               # noqa: E731
    else:
        plans = autotune.enumerate_plans(shape, budget)
        vol = lambda p: (p.b_blk * p.c_blk               # noqa: E731
                         * p.m_blk * p.oh_blk)
    plans.sort(key=lambda p: (p.t_model, -vol(p)))
    return plans[:max(1, k)]


def refine_plan(shape, *, top_k: Optional[int] = None,
                vmem_budget: Optional[int] = None,
                opts: Optional[MeasureOptions] = None
                ) -> Tuple[object, List[dict]]:
    """Guided measured refinement of one layer: measure the top-K
    modeled candidates, return ``(measured_best, records)``.

    The ``launch/hillclimb.py`` shape — a modeled hypothesis list, a
    measurement per hypothesis, pick by stopwatch — applied per layer.
    ``records`` is one dict per candidate (modeled rank order) with the
    plan, its measured record, and whether the model's ranking survived
    measurement (``records[0]["model_pick"]``).
    """
    opts = opts or MeasureOptions()
    k = opts.top_k if top_k is None else top_k
    kind = "gemm" if type(shape).__name__ == "GemmShape" else "conv"
    cands = shortlist(shape, k, vmem_budget=vmem_budget)
    records = []
    for rank, plan in enumerate(cands):
        rec = measure_record(kind, shape, plan, opts=opts)
        records.append({"rank_model": rank, "plan": plan.to_dict(),
                        "model_pick": rank == 0, **rec})
    best_i = min(range(len(cands)),
                 key=lambda i: records[i]["t_measured"])
    return cands[best_i], records


def profile_table(table, *, opts: Optional[MeasureOptions] = None,
                  trace=None, t0: Optional[float] = None):
    """Measure every plan row of a table -> the format-3 measured table.

    Each row's ``(shape, plan)`` goes through :func:`measure_record`
    (cache-memoised, counted in ``autotune.measure_stats``); the
    returned table carries per-row ``measured`` dicts plus
    ``provenance["measurement"]`` — backend fingerprint, harness
    parameters, and the measure-stat delta this pass actually ran
    (a warm recompile's delta shows pure cache hits). With ``trace``,
    one ``measure`` span per plan lands on the ``compile`` track,
    wall-clock relative to ``t0`` (defaulting to now).
    """
    from repro.kernels import autotune
    from repro.obs.trace import CAT_COMPILE, COMPILE_TRACK
    from repro.pipeline.plan_table import plan_key

    opts = opts or MeasureOptions()
    interpret = opts.resolve_interpret()
    before = autotune.measure_stats()
    origin = time.perf_counter() if t0 is None else t0
    measured: Dict[str, dict] = {}
    for kind, rows, mk_shape, mk_plan in (
            ("conv", table.conv, autotune.ConvShape, autotune.ConvPlan),
            ("gemm", table.gemm, autotune.GemmShape, autotune.GemmPlan)):
        for row in rows:
            shape = mk_shape(**row["shape"])
            plan = mk_plan(**row["plan"])
            ts = time.perf_counter() - origin
            rec = measure_record(kind, shape, plan, opts=opts)
            if trace is not None:
                trace.span("measure", ts,
                           time.perf_counter() - origin,
                           track=COMPILE_TRACK, cat=CAT_COMPILE,
                           args={"kind": kind,
                                 "plan": row["plan"],
                                 "t_measured": rec["t_measured"],
                                 "t_model_call": rec["t_model_call"]})
            measured[plan_key(row)] = rec
    after = autotune.measure_stats()
    provenance = dict(table.provenance)
    provenance["measurement"] = {
        "backend": backend_fingerprint(interpret),
        "harness": opts.harness(),
        "measure_stats": {k: after[k] - before[k] for k in sorted(after)},
    }
    return table.with_measurements(measured, provenance=provenance)
