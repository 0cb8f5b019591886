"""A kernel's share of its roofline, from the trace and the counts."""
from __future__ import annotations

from typing import Optional

from bench import tracing
from bench.counts import least_time
from bench.peaks import flop_rate, peaks


def kernel_share(ctx, families, layer_kind: str) -> Optional[float]:
    """100 x (least time of the ``layer_kind`` layers run) / (device time
    of the kernel's events, the compiled kernels of ``families``). Each
    forward runs one event per such layer on each chip, so the events
    count the layers run. None where the trace holds no such event."""
    if ctx.trace is None:
        return None
    ops = tracing.kernel_ops(ctx.trace, families)
    if not ops:
        return None
    cfg = ctx.cell.cfg
    t_least, n_layers = least_time(
        cfg, layer_kind, ctx.cell.traffic["batch_per_chip"],
        flop_rate(ctx.device_kind, cfg["precision"]["dtype"]),
        peaks(ctx.device_kind)["hbm_bw"])
    device_s = sum(o.end - o.start for o in ops) * 1e-9
    return 100.0 * (len(ops) / n_layers) * t_least / device_s
