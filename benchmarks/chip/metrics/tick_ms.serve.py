"""Median host time of one Engine.step in the traced window."""
from benchmarks.chip import readers


def read(ctx):
    return readers.span_ms(ctx, "engine_step")
