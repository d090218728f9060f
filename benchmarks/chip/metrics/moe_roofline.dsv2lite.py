"""Share of its bandwidth roofline that the MoE layers reached in the
traced ticks: the routers', held experts' and shared experts' weight bytes
of each tick (``flops_mla_moe.moe_weight_bytes``) over the chip's HBM
bandwidth, against the device time of the ops under the program's ``moe``
scope (``scopes.py``)."""
from benchmarks.chip import scopes


def read(ctx):
    return scopes.roofline(ctx, "moe", ctx.get("moe_bytes"))
