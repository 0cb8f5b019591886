"""Python's garbage collector in the traced window: the summed length
of the program's ``py.gc`` spans (generation-1 and -2 collections),
in ms (stream cells)."""
from bench import program_spans


def read(ctx):
    return program_spans.total_ms(ctx, "py.gc")
