"""95th-percentile latency (nearest rank) over all requests of the
window, from each request's scheduled arrival to its logits on the
host."""
from bench.stats import nearest_rank


def read(ctx):
    return nearest_rank(ctx.window.latencies_s, 0.95) * 1e3
