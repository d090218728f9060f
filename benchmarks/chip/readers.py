"""Reductions that the per-layer metric files share. Each returns None
where the run has nothing to read, so that the metric is left out; none
returns 0 for a share of a roofline or a peak."""
from __future__ import annotations

from typing import Optional

from benchmarks.chip import flops, peaks


def _trace(ctx: dict):
    tr = ctx.get("trace")
    return tr if tr is not None and tr.ops and tr.window_s > 0 else None


def span_ms(ctx: dict, name: str) -> Optional[float]:
    spans = ctx.get("spans")
    return spans.median_ms(name) if spans is not None else None


def device_idle(ctx: dict) -> Optional[float]:
    """Percent of the traced window in which no op ran on the device."""
    tr = _trace(ctx)
    return None if tr is None else 100.0 * (1.0 - tr.busy_s() / tr.window_s)


def kernel_roofline(ctx: dict, prefix: str) -> Optional[float]:
    """Percent of its roofline that a memory-bound kernel reached: the
    bytes its events read and wrote (from each event's instruction
    shapes) over the chip's HBM bandwidth, against their summed device
    time. The quantize kernels do a few operations per element, so bytes
    bound them and operations are not counted."""
    tr = _trace(ctx)
    events = tr.events(prefix) if tr is not None else []
    seconds = sum(s for s, _ in events)
    if seconds <= 0:
        return None
    least = sum(flops.hlo_bytes(n) for _, n in events) / \
        peaks.lookup(ctx["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * least / seconds


def step_mfu(ctx: dict) -> Optional[float]:
    """The plain model's forward operations done in the traced window as
    a percent of the chip's bf16 peak over the window."""
    tr = _trace(ctx)
    if tr is None or not ctx.get("model_flops"):
        return None
    return peaks.share(ctx["model_flops"], tr.window_s,
                       peaks.lookup(ctx["device_kind"])["bf16_flops_per_s"])
