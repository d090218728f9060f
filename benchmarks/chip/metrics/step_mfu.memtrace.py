"""One plain causal forward per traced step (profiling lanes do not
count) over window time x peak."""
from benchmarks.chip import readers


def read(ctx):
    return readers.step_mfu(ctx)
