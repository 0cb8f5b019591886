"""Median wait of a request from its scheduled arrival to the dispatch
of its batch (the batcher's queue)."""
import statistics


def read(ctx):
    q = ctx.window.queue_s
    return statistics.median(q) * 1e3 if q else None
