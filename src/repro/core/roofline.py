"""Three-term roofline analysis from a compiled (AOT) XLA artifact.

    T_compute    = FLOPs_global      / (chips * PEAK_FLOPS)
    T_memory     = HBM_bytes_global  / (chips * HBM_BW)
    T_collective = collective_bytes  / (chips * ICI_BW)

``cost_analysis()`` on an SPMD-partitioned module reports *per-device*
numbers; we multiply back by chip count to report global terms (the division
in the formulas then cancels — both conventions are recorded).

collective_bytes comes from parsing the post-partitioning HLO: the summed
operand sizes of all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute instructions.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, Optional

# --- per-chip peaks, keyed by jax's ``device_kind`` ---
# TPU v5e ("TPU v5 lite"): Google Cloud documentation, "TPU v5e" — 197
# TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s of
# inter-chip interconnect (4 links of 50 GB/s).
DEVICE_PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bw": 819e9, "hbm_bytes": 16e9, "ici_bw": 50e9},
}
# the device the roofline model and the tuner price plans for
TARGET_DEVICE_KIND = "TPU v5 lite"


def device_peaks(kind: str) -> Dict[str, float]:
    """The published peaks of ``kind`` (a ``device_kind`` string).

    A device that is not in :data:`DEVICE_PEAKS` is an error, not a
    default: a roofline share against the wrong chip's peak is noise.
    """
    try:
        return DEVICE_PEAKS[kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {kind!r}; "
                       f"known: {sorted(DEVICE_PEAKS)}") from None


_TARGET = device_peaks(TARGET_DEVICE_KIND)
PEAK_FLOPS = _TARGET["bf16_flops"]
PEAK_OPS_INT8 = _TARGET["int8_ops"]   # int8 MACs run at 2x the bf16 rate
HBM_BW = _TARGET["hbm_bw"]            # bytes/s
ICI_BW = _TARGET["ici_bw"]            # bytes/s per link
VMEM_BYTES = 16 * 2 ** 20    # Mosaic's default scoped-VMEM limit per kernel
MXU_DIM = 128                # systolic array dimension


def peak_ops(dtype: str = "bfloat16") -> float:
    """Peak MXU op rate for a compute dtype.

    int8 is the only dtype with a distinct rate in this model (2x bf16 —
    the TPU analogue of PipeCNN's fixed-point DSP saving); fp32 is kept
    at PEAK_FLOPS so pre-quantization trajectories stay comparable.
    """
    return PEAK_OPS_INT8 if dtype == "int8" else PEAK_FLOPS


def cost_analysis_dict(compiled) -> dict:
    """``compiled.cost_analysis()`` as a dict (empty when XLA gives none)."""
    return compiled.cost_analysis() or {}


def mxu_utilization(c_blk: int, m_blk: int) -> float:
    """Fraction of the 128x128 MXU fed by a (c_blk, m_blk) conv tile.

    The contraction/output tile dims map onto the systolic array; tiles
    smaller than 128 leave lanes idle — the TPU analogue of the paper's
    VEC_SIZE channel-padding waste (Fig. 7's reason VEC=8 beats VEC=16).
    """
    return min(1.0, c_blk / MXU_DIM) * min(1.0, m_blk / MXU_DIM)


def time_bounds(flops: float, hbm_bytes: float, *,
                mxu_util: float = 1.0,
                dtype: str = "bfloat16") -> "tuple[float, float]":
    """(t_compute, t_memory) roofline terms for one kernel invocation.

    This is the per-kernel cost model the conv DSE autotuner scores plans
    with (kernels/autotune.py) — the same two terms as the whole-model
    roofline above, restricted to a single pallas_call. ``dtype`` is the
    COMPUTE dtype: int8 doubles the peak op rate (the operand byte
    counts are the caller's job — they shrink 4x vs fp32, which is what
    moves the roofline balance point and makes the tuner pick genuinely
    different int8 plans).
    """
    return (flops / (peak_ops(dtype) * max(mxu_util, 1e-9)),
            hbm_bytes / HBM_BW)

def pipeline_bubble_fraction(n_stages: int, n_microbatches: int) -> float:
    """GPipe fill-drain bubble fraction: (S-1)/(M+S-1).

    The fraction of a pipeline round spent filling/draining rather than
    at full stage occupancy — the serving engine's stage planner and the
    fleet benchmarks use it to model pipeline-parallel round time as
    ``(M + S - 1) * t_stage_max``.
    """
    return (n_stages - 1) / (n_microbatches + n_stages - 1)


_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1, "u64": 8, "u32": 4, "u16": 2,
    "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

# one tensor type, e.g. bf16[16,4096,128]{2,1,0}
_SHAPE_RE = re.compile(r"\b(" + "|".join(_DTYPE_BYTES) + r")\[([\d,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?(%[\w.\-]+)\s*=\s*(.*)$")
_COLL_CALL_RE = re.compile(
    r"\b(" + "|".join(_COLLECTIVES) + r")(-start)?\(([^)]*)\)")


def collective_bytes_from_hlo(hlo_text: str) -> Dict[str, int]:
    """Sum operand bytes per collective kind from post-SPMD HLO text.

    XLA references operands by %name (no inline type), so this makes one
    pass building name -> bytes from each definition's output type, then a
    second pass resolving collective operands. `-done` halves of async
    pairs are skipped (the `-start` carries the operands). Ops inside
    while-loop bodies are counted ONCE — callers account for trip counts
    (the roofline runs use unrolled layers; see launch/dryrun.py --unroll).
    """
    name_bytes: Dict[str, int] = {}
    lines = hlo_text.splitlines()
    for line in lines:
        m = _DEF_RE.match(line)
        if not m:
            continue
        rhs = m.group(2)
        shapes = _SHAPE_RE.findall(rhs.split("(", 1)[0])  # output type only
        name_bytes[m.group(1)] = sum(_shape_bytes(d, s) for d, s in shapes)

    out = {k: 0 for k in _COLLECTIVES}
    for line in lines:
        m = _COLL_CALL_RE.search(line)
        if not m or "-done(" in line:
            continue
        kind, _, operands = m.groups()
        total = 0
        for tok in operands.split(","):
            tok = tok.strip()
            # operand may be "%name" or "f32[...] %name"
            inline = _SHAPE_RE.findall(tok)
            if inline:
                total += sum(_shape_bytes(d, s) for d, s in inline)
            else:
                nm = tok.split(" ")[-1]
                total += name_bytes.get(nm, 0)
        out[kind] += total
    return out


@dataclasses.dataclass
class RooflineReport:
    arch: str
    shape: str
    mesh: str
    chips: int
    flops_per_device: float
    bytes_per_device: float
    collective_bytes_per_device: float
    coll_breakdown: Dict[str, int]
    peak_memory_per_device: float
    model_flops: float                     # 6·N·D or serving equivalent

    @property
    def t_compute(self) -> float:
        return self.flops_per_device / PEAK_FLOPS

    @property
    def t_memory(self) -> float:
        return self.bytes_per_device / HBM_BW

    @property
    def t_collective(self) -> float:
        return self.collective_bytes_per_device / ICI_BW

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def t_bound(self) -> float:
        return max(self.t_compute, self.t_memory, self.t_collective)

    @property
    def useful_flops_ratio(self) -> float:
        hlo_global = self.flops_per_device * self.chips
        return self.model_flops / hlo_global if hlo_global else 0.0

    @property
    def roofline_fraction(self) -> float:
        """Fraction of the compute roofline achieved at the bound:
        useful-FLOPs time / bound time."""
        t_useful = self.model_flops / (self.chips * PEAK_FLOPS)
        return t_useful / self.t_bound if self.t_bound else 0.0

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d.update(t_compute=self.t_compute, t_memory=self.t_memory,
                 t_collective=self.t_collective, bottleneck=self.bottleneck,
                 useful_flops_ratio=self.useful_flops_ratio,
                 roofline_fraction=self.roofline_fraction)
        return d


def analyze_compiled(compiled, *, arch: str, shape: str, mesh_name: str,
                     chips: int, model_flops: float) -> RooflineReport:
    ca = cost_analysis_dict(compiled)
    flops = float(ca.get("flops", 0.0))
    byts = float(ca.get("bytes accessed", 0.0))
    hlo = compiled.as_text()
    # SPMD emits one single-program module: parsed operand bytes are already
    # the per-device contribution of each collective.
    coll = collective_bytes_from_hlo(hlo)
    ma = compiled.memory_analysis()
    peak = float(ma.temp_size_in_bytes + ma.argument_size_in_bytes +
                 ma.output_size_in_bytes - ma.alias_size_in_bytes)
    return RooflineReport(
        arch=arch, shape=shape, mesh=mesh_name, chips=chips,
        flops_per_device=flops, bytes_per_device=byts,
        collective_bytes_per_device=float(sum(coll.values())),
        coll_breakdown=coll, peak_memory_per_device=peak,
        model_flops=model_flops)
