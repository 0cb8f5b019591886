"""Published per-chip peaks, keyed by JAX's ``device_kind``.

TPU v5e ("TPU v5 lite"): Google Cloud documentation, "TPU v5e" — 197
TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s. v5e publishes
no float32 rate: float32 work is priced at the bf16 peak, so a float32
dot at HIGHEST precision (several MXU passes) reads well under 100 %.
"""
from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bw": 819e9, "hbm_bytes": 16e9},
}


def peaks(kind: str) -> Dict[str, float]:
    """The peaks of ``kind``; a kind not in the table is an error."""
    try:
        return PEAKS[kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {kind!r}; "
                       f"known: {sorted(PEAKS)}") from None


def flop_rate(kind: str, dtype: str) -> float:
    """Peak operations/s for work in ``dtype`` on ``kind``."""
    p = peaks(kind)
    return p["int8_ops"] if dtype == "int8" else p["bf16_flops"]
