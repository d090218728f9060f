"""The program's ``raptor/`` spans as ``benchmarks/chip/program_spans.py``
reads them, on a few traced ticks of ``danube-chat-shadow`` recorded on a
TPU v5e (``benchmarks/chip/testdata/serve_spans.xplane.pb.gz``: seven
ticks, three shadowed and four plain, cut from a 3-s traced window, with
the ``bench_window`` span set to them), and on the older fixture, which
predates the spans (``serve_ticks.xplane.pb.gz``)."""
import json
import math
import os
import statistics

import pytest

from benchmarks.chip import program_spans, readers, spec, xplane
from conftest import ROOT

TESTDATA = os.path.join(ROOT, "benchmarks", "chip", "testdata")
SPANS = os.path.join(TESTDATA, "serve_spans.xplane.pb.gz")
TICKS = os.path.join(TESTDATA, "serve_ticks.xplane.pb.gz")
READINGS = ["wait_idle_ms", "host_idle_ms", "logits_fetch_ms",
            "shadow_observe_ms", "emit_share"]
METRICS = [m["name"] for m in json.load(
    open(os.path.join(ROOT, "BENCHMARK.json")))["per_layer"]]


def _ctx(tr):
    return {"trace": tr, "device_kind": "TPU v5 lite", "model_flops": 1e9}


@pytest.fixture(scope="module")
def spans():
    return program_spans.ProgramTrace(SPANS, ("engine_step",))


def test_fixture_holds_both_paths(spans):
    paths = [c[3]["path"] for _, ch in spans.trees()
             for c in ch if c[2] == "raptor/engine.dispatch"]
    assert "shadow" in paths and "decode" in paths
    assert os.path.getsize(SPANS) < 300_000


@pytest.mark.parametrize("name", READINGS)
def test_new_readers_finite(spans, name):
    value = program_spans.summary(spans)[name]
    assert value is not None and math.isfinite(value) and value > 0
    if name == "emit_share":
        assert value <= 100


def test_idle_parts_fit_in_idle_per_tick(spans):
    """Idle inside ``engine.wait`` and elsewhere inside ``engine.step`` is
    at most the device's idle time per tick: the rest lies between ticks,
    in the harness's own loop."""
    tick_ms = statistics.median((e - s) * 1e-6 for s, e, n in spans.spans
                                if n == "engine_step")
    per_tick = readers.device_idle(_ctx(spans)) / 100 * tick_ms
    assert (program_spans.wait_idle_ms(spans)
            + program_spans.host_idle_ms(spans)) <= per_tick


def test_gaps_named_by_program_spans(spans):
    names = {n for n, _ in spans.breakdown()["idle_gaps"]}
    assert names and all(n.startswith("raptor/") for n in names)


@pytest.mark.parametrize("path", [SPANS, TICKS])
def test_existing_readers_unmoved_by_program_spans(path):
    """Every per-layer metric of the benchmark reads the same value on a
    trace that keeps the program's spans as on the harness's own
    reduction, which keeps none."""
    ctx = _ctx(program_spans.ProgramTrace(path, ("engine_step",)))
    bare = _ctx(xplane.Trace(path, ("engine_step",)))
    assert ctx["trace"].program if path == SPANS else \
        not ctx["trace"].program
    for name in METRICS:
        read = spec.reader(name)
        assert read(ctx) == read(bare), name


def test_old_fixture_reads_as_before():
    """The readings of the older fixture, as the harness read them before
    the program had spans."""
    tr = xplane.Trace(TICKS, ("engine_step",))
    ctx = _ctx(tr)
    assert readers.device_idle(ctx) == pytest.approx(14.281532906832318,
                                                     rel=1e-12)
    assert readers.kernel_roofline(ctx, "quantize_em") == pytest.approx(
        81.194523433359, rel=1e-12)
    assert readers.step_mfu(ctx) == pytest.approx(0.019741816979500608,
                                                  rel=1e-12)
    assert [n for n, _ in tr.breakdown()["idle_gaps"]][:1] == ["engine_step"]


@pytest.mark.parametrize("name", READINGS)
def test_new_readers_silent_without_program_spans(name):
    """A program without the spans (an older commit) reads nothing."""
    tr = program_spans.ProgramTrace(TICKS, ("engine_step",))
    assert program_spans.summary(tr)[name] is None
    assert [n for n, _ in tr.breakdown()["idle_gaps"]][:1] == ["engine_step"]


def test_intersect():
    assert program_spans.intersect([(0, 2), (5, 9)], [(1, 6)]) == 2
    assert program_spans.intersect([(0, 1)], [(1, 2)]) == 0
