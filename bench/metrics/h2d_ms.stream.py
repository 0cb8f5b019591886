"""Median host time in the program's copy of a batch to the device:
its ``cnn.h2d`` spans in the traced window (stream cells). The span
ends when the copy call returns, not when the copy lands."""
from bench import program_spans


def read(ctx):
    return program_spans.median_ms(ctx, "cnn.h2d")
