"""The whole forward's share of the chips' peak: model operations of
the images completed in the traced window over window x chips x peak."""
from bench.counts import flops_per_image
from bench.peaks import flop_rate


def read(ctx):
    if ctx.trace is None or not ctx.trace.devices:
        return None
    cfg = ctx.cell.cfg
    peak = flop_rate(ctx.device_kind, cfg["precision"]["dtype"])
    return 100.0 * ctx.window.images * flops_per_image(cfg) / (
        ctx.trace.window_s * ctx.chips * peak)
