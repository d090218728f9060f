"""Share of their bandwidth roofline that the quantize kernels reached in
the traced ticks."""
from benchmarks.chip import readers


def read(ctx):
    return readers.kernel_roofline(ctx, "quantize_em")
