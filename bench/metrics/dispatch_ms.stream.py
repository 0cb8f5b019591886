"""Median host time in the program's jitted call, up to its return of
logits not yet ready: its ``cnn.dispatch`` spans in the traced window
(stream cells)."""
from bench import program_spans


def read(ctx):
    return program_spans.median_ms(ctx, "cnn.dispatch")
