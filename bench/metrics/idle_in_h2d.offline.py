"""Device idle time that falls inside the program's ``cnn.h2d`` spans
(the host copying a batch to the device(s)), the mean over the cell's
chips, as a share of the traced window (offline cells)."""
from bench import program_spans


def read(ctx):
    placed = program_spans.read(ctx)
    if placed is None:
        return None
    share = program_spans.idle_overlap_share(ctx.trace, placed, "cnn.h2d")
    return None if share is None else 100.0 * share
