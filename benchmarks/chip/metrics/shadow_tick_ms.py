"""Median host time of the Engine.step ticks that had a shadowed request
live (the harness reads the slots and the queue before the step)."""
from benchmarks.chip import readers


def read(ctx):
    return readers.span_ms(ctx, "engine_step.shadowed")
