"""The plain model's forward FLOPs of the traced ticks (each live slot's
token, attention over its filled positions) over window time x peak."""
from benchmarks.chip import readers


def read(ctx):
    return readers.step_mfu(ctx)
