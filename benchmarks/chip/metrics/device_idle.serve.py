"""Share of the traced window with no op on the device."""
from benchmarks.chip import readers


def read(ctx):
    return readers.device_idle(ctx)
