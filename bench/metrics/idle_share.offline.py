"""Share of the traced window in which no operation ran on the device,
the mean over the cell's chips (offline cells)."""
from bench import tracing


def read(ctx):
    share = tracing.idle_share(ctx.trace)
    return None if share is None else 100.0 * share
