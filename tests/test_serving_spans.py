"""The serving engine's profiler spans, read back from the profiler's
trace (``benchmarks/chip/program_spans.py``): one ``raptor/engine.step`` tree
per tick with its phases and their counts; and the shadow report's counts,
merged on the host in int64 past 2^31."""
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core import TruncationPolicy
from repro.core.memmode import RaptorReport
from repro.models import Model
from repro.serving import Engine, ShadowConfig
from repro.serving.shadow import ShadowProfiler

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.chip import program_spans, xplane  # noqa: E402

PHASES = ["raptor/engine.admit", "raptor/engine.feed",
          "raptor/engine.dispatch", "raptor/engine.wait",
          "raptor/engine.fetch", "raptor/engine.sample",
          "raptor/engine.emit"]
SHADOW = ["raptor/shadow.observe", "raptor/shadow.check"]


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """A tiny engine with some requests shadowed, every tick under the
    profiler inside one ``bench_window`` span."""
    cfg = ArchConfig(name="spans", family="dense", n_layers=1, d_model=32,
                     n_heads=2, n_kv_heads=1, head_dim=16, d_ff=64, vocab=48,
                     dtype="float32", remat=False, scan_layers=False)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = Engine(model, params, batch_size=2, max_seq_len=24,
                 policy=TruncationPolicy.scoped("**/mlp", "e5m7"),
                 shadow=ShadowConfig(rate=0.5, seed=3))
    r = np.random.RandomState(0)
    reqs = [eng.submit(r.randint(1, cfg.vocab, n).astype(np.int32),
                       max_new_tokens=m)
            for n, m in [(3, 4), (5, 2), (2, 6), (4, 3), (6, 2)]]
    assert any(q.shadowed for q in reqs) and not all(q.shadowed for q in reqs)
    out = str(tmp_path_factory.mktemp("trace"))
    jax.profiler.start_trace(out)
    with jax.profiler.TraceAnnotation(xplane.WINDOW_SPAN):
        eng.run()
    jax.profiler.stop_trace()
    return eng, reqs, program_spans.ProgramTrace(xplane.find_trace(out))


def _names(children):
    return sorted(c[2] for c in children if c[2] != "raptor/api.trace")


def test_each_tick_is_one_step_tree(traced):
    eng, _, tr = traced
    trees = tr.trees("raptor/engine.step")
    assert [t[3]["step_num"] for t, _ in trees] == list(range(eng._tick))
    for _, children in trees:
        (dispatch,) = [c for c in children
                       if c[2] == "raptor/engine.dispatch"]
        want = PHASES + (SHADOW if dispatch[3]["path"] == "shadow" else [])
        assert _names(children) == sorted(want)
        assert dispatch[3]["live"] >= dispatch[3]["emitting"]
        (wait,) = [c for c in children if c[2] == "raptor/engine.wait"]
        (fetch,) = [c for c in children if c[2] == "raptor/engine.fetch"]
        assert dispatch[1] <= wait[0] and wait[1] <= fetch[0]
        # the logits, and on a shadowed tick the report with them
        logits = 2 * 48 * 4
        if dispatch[3]["path"] == "shadow":
            (observe,) = [c for c in children
                          if c[2] == "raptor/shadow.observe"]
            assert fetch[1] <= observe[0]
            assert fetch[3]["bytes"] > logits
        else:
            assert fetch[3]["bytes"] == logits


def test_shadow_spans_exactly_on_shadowed_ticks(traced):
    eng, _, tr = traced
    trees = tr.trees("raptor/engine.step")
    paths = [next(c[3]["path"] for c in ch
                  if c[2] == "raptor/engine.dispatch") for _, ch in trees]
    observed = [sum(c[2] == "raptor/shadow.observe" for c in ch)
                for _, ch in trees]
    assert "shadow" in paths and "decode" in paths
    assert observed == [int(p == "shadow") for p in paths]
    assert paths.count("shadow") == eng._shadow.shadow_ticks


def test_in_place_on_every_tick(traced):
    """``in_place`` is 1 on every tick, decode and shadowed alike: both
    steps take the cache donated and write their new rows in place."""
    _, _, tr = traced
    flags = {"decode": set(), "shadow": set()}
    for _, children in tr.trees("raptor/engine.step"):
        (dispatch,) = [c for c in children
                       if c[2] == "raptor/engine.dispatch"]
        flags[dispatch[3]["path"]].add(dispatch[3]["in_place"])
    assert flags == {"decode": {1}, "shadow": {1}}


def test_dispatch_counts_experts_and_latent_cache(traced):
    """The static ``experts_held`` and ``latent_cache_bytes`` ride every
    dispatch; a dense GQA model holds neither."""
    _, _, tr = traced
    seen = set()
    for _, children in tr.trees("raptor/engine.step"):
        for c in children:
            if c[2] == "raptor/engine.dispatch":
                seen.add((c[3]["experts_held"], c[3]["latent_cache_bytes"]))
    assert seen == {(0, 0)}


def test_emitted_counts_the_tokens_served(traced):
    _, reqs, tr = traced
    counts = {"emitted": 0, "finished": 0, "quarantined": 0}
    for _, children in tr.trees("raptor/engine.step"):
        for c in children:
            if c[2] == "raptor/engine.emit":
                for k in counts:
                    counts[k] += c[3][k]
    assert counts["emitted"] == sum(len(q.out_tokens) for q in reqs)
    assert counts["finished"] == len(reqs) and counts["quarantined"] == 0


def test_program_spans_join_the_gap_naming(traced):
    """Every program span is among the spans that name the idle gaps."""
    _, _, tr = traced
    named = {s[2] for s in tr.spans}
    assert set(PHASES + SHADOW + ["raptor/engine.step"]) <= named
    assert {s[2] for s in tr.program} <= named


class _Req:
    report = None


def test_shadow_counts_merge_past_int32():
    """Three ticks of 2^30 and 2^31 - 1 elements a location: int32 sums
    would wrap; the rolling and per-request reports hold them exactly."""
    prof = ShadowProfiler(lambda x: x * 2.0,
                          TruncationPolicy.everywhere("e5m2"),
                          ShadowConfig(rate=1.0))
    big = np.array([2**30, 2**31 - 1])
    tick = RaptorReport(("a", "b"), jnp.asarray(big, jnp.int32),
                        jnp.asarray([0.5, 0.25], jnp.float32),
                        jnp.asarray(big, jnp.int32))
    req = _Req()
    for t in range(3):
        prof.observe(tick, [req], t)
    for rep in (prof.report, req.report):
        assert rep.op_counts.dtype == np.int64
        assert rep.op_counts.tolist() == (3 * big).tolist()
        assert rep.flags.tolist() == (3 * big).tolist()
        assert rep.max_rel.tolist() == [0.5, 0.25]
    assert prof.shadow_ticks == 3
    assert prof.check(3) is None and prof.peak_rel() == 0.5
