"""Memtrace's step over the plain jax.jit(model.loss) step, both timed by
the host clock in the traced run's process: each the median interval
between two host reads of steps sent ahead, the device's time per step."""


def read(ctx):
    return ctx.get("memtrace_overhead_x")
