"""Head 1 — the static artifact verifier.

PipeCNN's design flow proves a configuration fits the FPGA *before*
synthesis: tile sizes and buffer depths are checked against the DSP /
BRAM budget at compile time. This module is that check for our stack:
given a committed :class:`~repro.pipeline.plan_table.PlanTable` (and
optionally the ``ExecutionSpec``/``CNNConfig`` it was compiled under, or
a whole ``CompiledCNN.save`` artifact directory), it statically re-proves
the invariants a serving fleet relies on — **without running a single
kernel or DSE sweep**:

* every conv/GEMM plan fits its declared VMEM budget, re-derived through
  the pure predicates ``autotune.plan_fits`` / ``autotune.gemm_plan_fits``
  (RPA301) and matches its recorded ``vmem_bytes`` (RPA302);
* block shapes tile their layer shapes: positive blocks, ``b_blk`` vs
  the serving batch, per-group channel bounds, and the ``conv_pipe``
  halo/line-buffer geometry (pooled ``oh_blk`` must be a ``pool_s``
  multiple or the kernel would silently run a different geometry than
  the committed row describes) (RPA303);
* dtypes and budgets are consistent with the Precision/Tiling spec —
  int8 specs get int8 plan rows and quantized params manifests carrying
  requantize scales, fp32 specs don't (RPA304);
* the fusion grouping partitions the layer stack and every fusion group
  has exactly one tuned plan at the serving (batch, dtype) key (RPA305);
* format-3 ``measured`` records reconcile with their rows over the
  shared ``plan_key`` join (RPA306);
* a saved artifact is structurally sound: commit marker, manifest
  format, reconstructable cfg/spec (``SpecError`` surfaces verbatim so
  a verifier finding reads exactly like the constructor rejection),
  leaf files present and accounted (RPA307).

Purity contract (asserted by ``tests/test_analysis.py``): only the
side-effect-free autotune model functions are called — never
``get_plan``/``best_plan`` — so ``sweep_stats``/``measure_stats`` are
unchanged by a verification pass.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import List, Optional

from repro.analysis.findings import Finding
from repro.core.config import CNNConfig, SpecError, fuse_groups
from repro.kernels.autotune import (ConvPlan, ConvShape, GemmPlan,
                                    GemmShape, _DTYPE_BYTES,
                                    conv_vmem_bytes, gemm_plan_fits,
                                    gemm_vmem_bytes, plan_fits)
from repro.kernels.conv_pipe import s2d_geometry

_ROW_FIELDS = ("shape", "backend", "vmem_budget", "plan")


def _mib(n: int) -> str:
    return f"{n / 2**20:.1f} MiB"


def _row(table, kind: str, i: int, path: str,
         shape_cls, plan_cls, findings: List[Finding]):
    """Decode row ``i`` or record RPA300 and return ``None``."""
    row = (table.conv if kind == "conv" else table.gemm)[i]
    loc = f"{path}#{kind}[{i}]"
    missing = [f for f in _ROW_FIELDS if f not in row]
    if missing:
        findings.append(Finding(
            "RPA300", loc, 0,
            f"plan row is missing field(s) {missing} — not a "
            f"registry-snapshot record"))
        return None
    try:
        shape = shape_cls(**row["shape"])
        plan = plan_cls(**row["plan"])
    except TypeError as e:
        findings.append(Finding(
            "RPA300", loc, 0, f"plan row does not decode as "
            f"({shape_cls.__name__}, {plan_cls.__name__}): {e}"))
        return None
    if not isinstance(row["vmem_budget"], int) or row["vmem_budget"] <= 0:
        findings.append(Finding(
            "RPA300", loc, 0,
            f"vmem_budget={row['vmem_budget']!r} is not a positive "
            f"byte count"))
        return None
    return loc, row, shape, plan


def _check_conv_row(loc: str, row: dict, shape: ConvShape, plan: ConvPlan,
                    spec, findings: List[Finding]) -> None:
    budget = row["vmem_budget"]
    if shape.dtype not in _DTYPE_BYTES:
        findings.append(Finding(
            "RPA304", loc, 0,
            f"shape dtype {shape.dtype!r} is not a pipeline dtype "
            f"({sorted(_DTYPE_BYTES)})"))
        return
    # -- block-shape / halo geometry (RPA303) -----------------------------
    bad_blocks = [n for n, v in (("c_blk", plan.c_blk),
                                 ("m_blk", plan.m_blk),
                                 ("b_blk", plan.b_blk)) if v < 1]
    if plan.oh_blk < 0:
        bad_blocks.append("oh_blk")
    if bad_blocks:
        findings.append(Finding(
            "RPA303", loc, 0,
            f"non-positive block size(s) {bad_blocks} in plan "
            f"{plan.to_dict()}"))
        return
    if plan.b_blk > shape.b:
        findings.append(Finding(
            "RPA303", loc, 0,
            f"b_blk={plan.b_blk} exceeds the serving batch b={shape.b} "
            f"the plan is keyed for — the grid would read past the "
            f"batch"))
    # c_blk counts the kernel's space-to-depth channels (s*s*C/G)
    geom = s2d_geometry(shape.h, shape.w, shape.c // shape.groups,
                        shape.kh, shape.kw, stride=shape.stride,
                        pad=shape.pad)
    cg = geom.c
    mg = shape.m // shape.groups
    if plan.c_blk > cg or plan.m_blk > mg:
        findings.append(Finding(
            "RPA303", loc, 0,
            f"channel blocks (c_blk={plan.c_blk}, m_blk={plan.m_blk}) "
            f"exceed the per-group channels (space-to-depth c/g={cg}, "
            f"m/g={mg}) — the committed plan over-declares its tile"))
    if shape.pool and plan.oh_blk and plan.oh_blk % shape.pool_s:
        findings.append(Finding(
            "RPA303", loc, 0,
            f"oh_blk={plan.oh_blk} is not a multiple of "
            f"pool_s={shape.pool_s}: conv_tile_geometry would round it "
            f"up, so the kernel would run a different line-buffer depth "
            f"than this row commits to"))
    else:
        # Re-derive the halo geometry and prove the H-tiling covers the
        # (pooled) output exactly once — the line-buffer feasibility
        # argument of the paper, re-run from the committed numbers.
        n_h, pr, _oh_ext, _hp, _step = geom.tiles(
            plan.oh_blk, pool=shape.pool, pool_k=shape.pool_k,
            pool_s=shape.pool_s)
        out_rows = ((shape.oh - shape.pool_k) // shape.pool_s + 1
                    if shape.pool else shape.oh)
        if n_h * pr < out_rows or (n_h - 1) * pr >= out_rows:
            findings.append(Finding(
                "RPA303", loc, 0,
                f"H-tiling (n_h={n_h}, rows/tile={pr}) does not cover "
                f"the {out_rows} output rows exactly once"))
    # -- VMEM budget (RPA301/302) -----------------------------------------
    vmem = conv_vmem_bytes(shape, plan.c_blk, plan.m_blk, plan.oh_blk,
                           plan.b_blk, budget)
    if not plan_fits(shape, plan, budget):
        findings.append(Finding(
            "RPA301", loc, 0,
            f"conv plan {plan.to_dict()} for shape {row['shape']} needs "
            f"{vmem} B VMEM ({_mib(vmem)}) > declared budget {budget} B "
            f"({_mib(budget)})"))
    elif plan.vmem_bytes and plan.vmem_bytes != vmem:
        findings.append(Finding(
            "RPA302", loc, 0,
            f"recorded vmem_bytes={plan.vmem_bytes} disagrees with the "
            f"VMEM model ({vmem} B) — the row was edited or the model "
            f"changed under it"))
    _check_spec_key(loc, row, shape.dtype, spec, findings)


def _check_gemm_row(loc: str, row: dict, shape: GemmShape, plan: GemmPlan,
                    spec, findings: List[Finding]) -> None:
    budget = row["vmem_budget"]
    if shape.dtype not in _DTYPE_BYTES:
        findings.append(Finding(
            "RPA304", loc, 0,
            f"shape dtype {shape.dtype!r} is not a pipeline dtype "
            f"({sorted(_DTYPE_BYTES)})"))
        return
    if min(plan.bm, plan.bn, plan.bk) < 1:
        findings.append(Finding(
            "RPA303", loc, 0,
            f"non-positive GEMM blocking {plan.to_dict()}"))
        return
    over = [f"{n}={v} > {d}" for n, v, d in (
        ("bm", plan.bm, shape.m), ("bn", plan.bn, shape.n),
        ("bk", plan.bk, shape.k)) if v > d]
    if over:
        findings.append(Finding(
            "RPA303", loc, 0,
            f"GEMM blocking exceeds the FC dims ({', '.join(over)}) — "
            f"the committed plan over-declares its tile"))
    vmem = gemm_vmem_bytes(shape, plan.bm, plan.bn, plan.bk)
    if not gemm_plan_fits(shape, plan, budget):
        findings.append(Finding(
            "RPA301", loc, 0,
            f"GEMM plan {plan.to_dict()} for shape {row['shape']} needs "
            f"{vmem} B VMEM ({_mib(vmem)}) > declared budget {budget} B "
            f"({_mib(budget)})"))
    elif plan.vmem_bytes and plan.vmem_bytes != vmem:
        findings.append(Finding(
            "RPA302", loc, 0,
            f"recorded vmem_bytes={plan.vmem_bytes} disagrees with the "
            f"VMEM model ({vmem} B)"))
    _check_spec_key(loc, row, shape.dtype, spec, findings)


def _check_spec_key(loc: str, row: dict, dtype: str, spec,
                    findings: List[Finding]) -> None:
    """Rows of a compiled artifact must be keyed at the spec's
    (dtype, budget) — int8 specs get int8 plans, fp32 specs don't."""
    if spec is None:
        return
    if dtype != spec.run_dtype:
        findings.append(Finding(
            "RPA304", loc, 0,
            f"plan tuned for dtype {dtype!r} but the Precision spec "
            f"runs {spec.run_dtype!r} (quant={spec.precision.quant!r})"))
    if row["vmem_budget"] != spec.tiling.vmem_budget:
        findings.append(Finding(
            "RPA304", loc, 0,
            f"plan tuned under vmem_budget={row['vmem_budget']} but "
            f"Tiling.vmem_budget={spec.tiling.vmem_budget}"))


def _check_measured(table, path: str, findings: List[Finding]) -> None:
    """Format-3 reconciliation: each measured record joins its row by
    ``plan_key`` unambiguously, and measured tables say where the
    numbers came from."""
    from repro.pipeline.plan_table import plan_key

    by_key = {}
    n_measured = 0
    for kind in ("conv", "gemm"):
        for i, row in enumerate(getattr(table, kind)):
            if not all(f in row for f in _ROW_FIELDS):
                continue        # already RPA300
            loc = f"{path}#{kind}[{i}]"
            measured = row.get("measured")
            if measured is None and "measured" in row:
                measured = {}   # present-but-null is malformed too
            if measured is not None:
                n_measured += 1
                t = measured.get("t_measured") if isinstance(measured, dict) \
                    else None
                if not isinstance(t, (int, float)) or t <= 0:
                    findings.append(Finding(
                        "RPA306", loc, 0,
                        f"measured record carries no positive t_measured "
                        f"(got {measured!r})"))
            key = plan_key(row)
            prev = by_key.setdefault(key, (loc, measured))
            if prev[1] is not None and measured is not None \
                    and prev[1] != measured:
                findings.append(Finding(
                    "RPA306", loc, 0,
                    f"two rows share plan_key but carry different "
                    f"measured records (see {prev[0]}) — the "
                    f"measurement join is ambiguous"))
    if n_measured and table.provenance \
            and "measurement" not in table.provenance:
        findings.append(Finding(
            "RPA306", path, 0,
            f"{n_measured} measured row(s) but "
            f"provenance['measurement'] (backend fingerprint) is "
            f"missing — the numbers cannot be attributed to a backend"))


def _check_coverage(table, cfg: CNNConfig, spec, path: str,
                    findings: List[Finding]) -> None:
    """The fusion grouping partitions the layers, and every group has
    exactly one tuned plan at the serving (batch, dtype, budget) key."""
    from repro.pipeline.compile import _group_shapes

    groups = fuse_groups(cfg.layers)
    flat = [i for g in groups for i in g]
    if sorted(flat) != list(range(len(cfg.layers))):
        findings.append(Finding(
            "RPA305", path, 0,
            f"fuse_groups does not partition the {len(cfg.layers)} "
            f"layers: covered indices {sorted(flat)}"))
        return
    if not (spec.use_pallas and spec.tiling.autotune):
        return      # reference path / manual tiling: no table contract
    index = {}
    for kind in ("conv", "gemm"):
        for row in getattr(table, kind):
            if not all(f in row for f in _ROW_FIELDS):
                continue
            k = (json.dumps(row["shape"], sort_keys=True),
                 row["vmem_budget"])
            index.setdefault(k, []).append(
                json.dumps(row["plan"], sort_keys=True))
    for group, kind, shape in _group_shapes(cfg, spec.serving.batch,
                                            spec.run_dtype):
        k = (json.dumps(dataclasses.asdict(shape), sort_keys=True),
             cfg.vmem_budget)
        plans = index.get(k, [])
        if not plans:
            findings.append(Finding(
                "RPA305", path, 0,
                f"fusion group {tuple(group)} ({kind}, "
                f"{dataclasses.asdict(shape)}) has no plan row at the "
                f"serving key (batch={spec.serving.batch}, "
                f"dtype={spec.run_dtype!r}, budget={cfg.vmem_budget})"))
        elif len(set(plans)) > 1:
            findings.append(Finding(
                "RPA305", path, 0,
                f"fusion group {tuple(group)} has {len(set(plans))} "
                f"distinct plans for one tuning key — seeding from this "
                f"table is ambiguous"))


def verify_plan_table(table, *, spec=None, cfg: Optional[CNNConfig] = None,
                      path: str = "plan_table") -> List[Finding]:
    """Statically verify one :class:`PlanTable`.

    ``spec``/``cfg`` unlock the spec-consistency and coverage checks; a
    bare table still gets the budget / geometry / measurement passes.
    ``path`` is only a locator prefix for the findings.
    """
    findings: List[Finding] = []
    for kind, shape_cls, plan_cls, check in (
            ("conv", ConvShape, ConvPlan, _check_conv_row),
            ("gemm", GemmShape, GemmPlan, _check_gemm_row)):
        for i in range(len(getattr(table, kind))):
            dec = _row(table, kind, i, path, shape_cls, plan_cls, findings)
            if dec is not None:
                check(*dec, spec, findings)
    _check_measured(table, path, findings)
    if cfg is not None and spec is not None:
        _check_coverage(table, cfg, spec, path, findings)
    return findings


def verify_artifact(path) -> List[Finding]:
    """Statically verify a ``CompiledCNN.save`` artifact directory.

    Pure reads: the artifact is never compiled, no kernel runs. A
    rejected cfg/spec surfaces the :class:`SpecError` text verbatim, so
    the finding reads exactly like the constructor rejection would.
    """
    from repro.pipeline.artifact import cfg_from_dict, spec_from_dict
    from repro.pipeline.plan_table import PlanTable

    root = Path(path)
    loc = str(root)
    findings: List[Finding] = []
    if not root.is_dir():
        return [Finding("RPA307", loc, 0, "not a directory")]
    if not (root / "_COMMITTED").exists():
        findings.append(Finding(
            "RPA307", loc, 0,
            "no _COMMITTED marker — crashed save, or not an artifact "
            "directory"))
    man_path = root / "manifest.json"
    if not man_path.exists():
        findings.append(Finding("RPA307", loc, 0, "manifest.json missing"))
        return findings
    try:
        manifest = json.loads(man_path.read_text())
    except ValueError as e:
        findings.append(Finding(
            "RPA307", loc, 0, f"manifest.json is not JSON: {e}"))
        return findings
    if manifest.get("format") != 1:
        findings.append(Finding(
            "RPA307", loc, 0,
            f"manifest format {manifest.get('format')!r}, this verifier "
            f"understands 1"))
        return findings
    cfg = spec = None
    try:
        cfg = cfg_from_dict(manifest["cfg"])
        spec = spec_from_dict(manifest["spec"])
    except SpecError as e:
        findings.append(Finding(
            "RPA307", loc, 0, f"manifest rejects reconstruction "
            f"({e.field}): {e}"))
    except Exception as e:
        findings.append(Finding(
            "RPA307", loc, 0, f"manifest cfg/spec does not reconstruct: "
            f"{e!r}"))
    findings.extend(_check_params_manifest(
        root, manifest.get("params"), spec, loc))
    table_path = root / "plan_table.json"
    if not table_path.exists():
        findings.append(Finding(
            "RPA307", loc, 0, "plan_table.json missing"))
        return findings
    try:
        table = PlanTable.from_json(table_path.read_text())
    except ValueError as e:
        findings.append(Finding(
            "RPA307", str(table_path), 0, f"plan table rejected: {e}"))
        return findings
    findings.extend(verify_plan_table(table, spec=spec, cfg=cfg,
                                      path=str(table_path)))
    return findings


def _check_params_manifest(root: Path, pman, spec,
                           loc: str) -> List[Finding]:
    findings: List[Finding] = []
    if not isinstance(pman, dict) or "leaves" not in pman \
            or "layers" not in pman:
        findings.append(Finding(
            "RPA307", loc, 0,
            "params manifest missing (no layers/leaves record)"))
        return findings
    fmt = pman.get("format")
    if fmt not in ("fp32", "int8"):
        findings.append(Finding(
            "RPA307", loc, 0, f"params format {fmt!r}: fp32 or int8"))
        return findings
    if spec is not None:
        want = "int8" if spec.precision.quant == "int8" else "fp32"
        if fmt != want:
            findings.append(Finding(
                "RPA304", loc, 0,
                f"params are {fmt} but Precision.quant="
                f"{spec.precision.quant!r} compiles a {want} pipeline"))
    n_leaves = len(pman["leaves"])
    used: List[int] = []
    for i, layer in enumerate(pman["layers"]):
        if layer is None:
            continue
        if fmt == "int8":
            arrays = layer.get("arrays", {})
            used.extend(v for v in arrays.values() if v is not None)
            # weightless quantized layers (pool/lrn) carry all-null
            # arrays by design; only weighted kinds need int8 codes
            if layer.get("kind") in ("conv", "fc") \
                    and arrays.get("w_q") is None:
                findings.append(Finding(
                    "RPA304", loc, 0,
                    f"int8 layer {i} carries no quantized weight "
                    f"(arrays.w_q is null) — a fixed-point pipeline "
                    f"needs int8 codes + requantize scales"))
        else:
            used.extend(v for v in (layer.get("w"), layer.get("b"))
                        if v is not None)
    bad = sorted(v for v in used if not isinstance(v, int)
                 or not 0 <= v < n_leaves)
    if bad:
        findings.append(Finding(
            "RPA307", loc, 0,
            f"leaf indices {bad} outside the {n_leaves} recorded leaves"))
    missing = sorted(i for i in set(used) - set(bad)
                     if not (root / f"leaf_{i}.npy").exists())
    if missing:
        findings.append(Finding(
            "RPA307", loc, 0,
            f"leaf file(s) missing on disk: "
            f"{[f'leaf_{i}.npy' for i in missing]}"))
    return findings


def verify_compiled(compiled) -> List[Finding]:
    """Verify a live ``CompiledCNN`` (``CompiledCNN.verify()`` calls
    this): its plan table against its own spec/cfg, plus the stage plan
    covering every fusion group exactly once."""
    findings = verify_plan_table(compiled.plans(), spec=compiled.spec,
                                 cfg=compiled.cfg,
                                 path=f"compiled:{compiled.cfg.name}")
    staged = [tuple(g) for stage in compiled.stages for g in stage]
    want = [tuple(g) for g in fuse_groups(compiled.cfg.layers)]
    if sorted(staged) != sorted(want) or len(staged) != len(want):
        findings.append(Finding(
            "RPA305", f"compiled:{compiled.cfg.name}", 0,
            f"stage plan does not cover every fusion group exactly "
            f"once: staged {staged} vs groups {want}"))
    return findings
