"""Batches of the window whose dispatch-to-logits time was more than
ten times the median batch's: stalls of the path between the host and
the device, which lengthen every wait queued behind them."""
import statistics

STALL = 10.0


def read(ctx):
    b = ctx.window.batch_s
    if not b:
        return None
    med = statistics.median(b)
    return sum(1 for t in b if t > STALL * med)
