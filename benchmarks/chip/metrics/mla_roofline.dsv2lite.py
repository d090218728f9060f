"""Share of its bandwidth roofline that latent attention reached in the
traced ticks: every layer's attention weights, the latent rows of each
live slot's filled positions and one written row per live slot
(``flops_mla_moe.mla_bytes``, counted from the work, not from the cache
buffer) over the chip's HBM bandwidth, against the device time of the ops
under the program's ``mla`` scope (``scopes.py``)."""
from benchmarks.chip import scopes


def read(ctx):
    return scopes.roofline(ctx, "mla", ctx.get("mla_bytes"))
