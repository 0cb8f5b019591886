"""Median wall time per batch from dispatch to logits on the host: each
bench.forward span's start to the end of the bench.fetch span after
it."""
import statistics


def read(ctx):
    if ctx.trace is None:
        return None
    times, start = [], None
    for name, s, e in ctx.trace.spans:
        if name == "bench.forward":
            start = s
        elif name == "bench.fetch" and start is not None:
            times.append((e - start) * 1e-6)
            start = None
    return statistics.median(times) if times else None
