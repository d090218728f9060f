"""The trace reduction, on a few ticks of the shadow cell recorded on a
TPU v5e (``benchmarks/chip/testdata/serve_ticks.xplane.pb.gz``), and on
hand-made intervals."""
import os

import pytest

from benchmarks.chip import flops, readers, xplane
from conftest import ROOT

FIXTURE = os.path.join(ROOT, "benchmarks", "chip", "testdata",
                       "serve_ticks.xplane.pb.gz")


@pytest.fixture(scope="module")
def trace():
    return xplane.Trace(FIXTURE, ("engine_step",))


def test_window_and_busy(trace):
    assert trace.window_s > 0
    assert 0 < trace.busy_s() < trace.window_s
    assert set(trace.ops) == {0}
    steps = [s for s in trace.spans if s[2] == "engine_step"]
    assert steps and all(trace.t0 <= s[0] and s[1] <= trace.t1 for s in steps)


def test_kernels_found_by_name(trace):
    events = trace.events("quantize_em")
    assert events
    assert all(flops.hlo_bytes(n) > 0 for _, n in events)
    ops = trace.op_time_s()
    assert "quantize_em" in ops and "while" not in ops
    assert ops["quantize_em"] == pytest.approx(sum(s for s, _ in events))


def test_shares_stay_in_range(trace):
    ctx = {"trace": trace, "device_kind": "TPU v5 lite", "model_flops": 1e9}
    roof = readers.kernel_roofline(ctx, "quantize_em")
    assert 0 < roof <= 100
    assert 0 < readers.device_idle(ctx) < 100
    assert 0 < readers.step_mfu(ctx) < 100


def test_gaps_named_by_the_span_around_them(trace):
    gaps = trace.idle_gaps()
    idle = sum(e - s for s, e, _ in gaps) * 1e-9
    assert idle == pytest.approx(trace.window_s - trace.busy_s(), rel=1e-6)
    assert {n for _, _, n in gaps} <= {"engine_step", "between harness spans"}
    b = trace.breakdown()
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    lengths = [s for _, s in b["idle_gaps"]]
    assert lengths == sorted(lengths, reverse=True)


def test_union_counts_overlaps_once():
    assert xplane._union([(0, 2), (1, 3), (5, 6), (6, 7)]) == [(0, 3), (5, 7)]
    assert xplane.op_key("%fusion.12 = f32[2]{0} fusion(...)") == "fusion"
