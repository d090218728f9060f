"""Continuous-batching serving: ragged admission into a busy batch,
bit-identity with isolated decoding, the zero-recompile discipline,
sampled shadow profiling with drift detection, and the unified
policy-resolution / profiling API surface (submit handles, keyword-only
thresholds, shared ``resolve_policy``)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.artifacts import PolicyArtifact, Registry
from repro.configs.base import ArchConfig
from repro.core import TruncationPolicy
from repro.core.api import memtrace, profile_counts, profile_trajectory
from repro.core.policy import ResolvedPolicy, parse_policy, resolve_policy
from repro.models import Model
from repro.serving import DriftEvent, Engine, Request, ShadowConfig


@pytest.fixture(scope="module")
def lm():
    cfg = ArchConfig(name="srv", family="dense", n_layers=2, d_model=48,
                     n_heads=4, n_kv_heads=2, head_dim=12, d_ff=96, vocab=64,
                     dtype="float32", remat=False, scan_layers=False)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _ragged_workload(cfg, seed=0, n=5):
    """Prompts of mixed length with mixed token budgets — the shape aligned
    waves cannot serve without padding every request to the longest."""
    r = np.random.RandomState(seed)
    lens = [3, 7, 5, 9, 2][:n]
    budgets = [4, 6, 3, 5, 8][:n]
    return [(r.randint(1, cfg.vocab, L).astype(np.int32), m)
            for L, m in zip(lens, budgets)]


def _isolated_outputs(model, params, workload, policy=None):
    """Reference: each request decoded alone in a batch-1 engine."""
    outs = []
    for prompt, m in workload:
        eng = Engine(model, params, batch_size=1, max_seq_len=32,
                     policy=policy)
        eng.submit(prompt, max_new_tokens=m)
        done = eng.run()
        outs.append(tuple(done[0].out_tokens))
    return outs


# --------------------------------------------------------------------------
# ragged admission + bit-identity
# --------------------------------------------------------------------------

def test_mixed_prompt_lengths_one_batch(lm):
    """Requests with different prompt lengths coexist in one decode batch:
    nothing waits for a wave, and every request runs to its own budget."""
    cfg, model, params = lm
    workload = _ragged_workload(cfg)
    eng = Engine(model, params, batch_size=3, max_seq_len=32)
    handles = [eng.submit(p, max_new_tokens=m) for p, m in workload]
    done = eng.run()
    assert len(done) == len(workload)
    for h, (_, m) in zip(handles, workload):
        assert h.done and h.status == "ok"
        assert len(h.out_tokens) == m
    assert done[handles[0].rid] is handles[0]   # dict returns the handles


def test_continuous_bit_identical_to_isolated(lm):
    """The acceptance bar: output tokens of continuous-batched decode are
    bit-identical to decoding each request alone — masked prefill into a
    busy batch and per-slot cursors change scheduling, never values."""
    cfg, model, params = lm
    workload = _ragged_workload(cfg)
    ref = _isolated_outputs(model, params, workload)
    eng = Engine(model, params, batch_size=3, max_seq_len=32)
    handles = [eng.submit(p, max_new_tokens=m) for p, m in workload]
    eng.run()
    assert [tuple(h.out_tokens) for h in handles] == ref


def test_continuous_bit_identical_under_policy(lm):
    cfg, model, params = lm
    pol = TruncationPolicy.scoped("**/mlp", "e5m4")
    workload = _ragged_workload(cfg, seed=1)
    ref = _isolated_outputs(model, params, workload, policy=pol)
    eng = Engine(model, params, batch_size=2, max_seq_len=32, policy=pol)
    handles = [eng.submit(p, max_new_tokens=m) for p, m in workload]
    eng.run()
    assert [tuple(h.out_tokens) for h in handles] == ref


def test_midstream_admission_into_freed_slot(lm):
    """More requests than slots: the queue drains into slots as they free
    mid-stream, while the other slot keeps decoding — and the jit cache
    never grows past one entry per path."""
    cfg, model, params = lm
    workload = _ragged_workload(cfg)          # 5 requests, 2 slots
    eng = Engine(model, params, batch_size=2, max_seq_len=32)
    handles = [eng.submit(p, max_new_tokens=m) for p, m in workload]
    ticks = 0
    admitted_midstream = False
    while eng.step():
        ticks += 1
        live = [s for s in eng.slots if s is not None]
        # once the first finishers drain, later submissions are live while
        # earlier ones still decode
        if any(h.done for h in handles) and any(
                not h.done and h in live for h in handles[2:]):
            admitted_midstream = True
    assert admitted_midstream
    assert all(h.done for h in handles)
    sizes = eng.cache_sizes()
    assert sizes["decode"] == 1 and sizes["reset"] == 1
    # no wave barrier: total ticks well under the sum of per-request spans
    spans = [len(p) + m for p, m in workload]
    assert ticks < sum(spans)


def test_quarantined_slot_immediately_reusable(lm):
    """A quarantined request frees its slot for the next admission on the
    same tick cadence as a healthy completion."""
    cfg, model, params = lm
    poisoned = jax.tree_util.tree_map(lambda p: p * jnp.nan, params)
    eng = Engine(model, poisoned, batch_size=2, max_seq_len=16)
    handles = [eng.submit(np.arange(1, 4, dtype=np.int32), max_new_tokens=4)
               for _ in range(3)]
    done = eng.run()
    assert len(done) == 3                     # the 3rd got a recycled slot
    for h in handles:
        assert h.done and h.status == "error_nonfinite"
        assert "quarantined" in h.error
    assert all(s is None for s in eng.slots)


# --------------------------------------------------------------------------
# engine handles: auto-rid, legacy shim, stream()
# --------------------------------------------------------------------------

def test_submit_returns_handle_with_auto_rid(lm):
    cfg, model, params = lm
    eng = Engine(model, params, batch_size=2, max_seq_len=16)
    a = eng.submit(np.array([1, 2, 3]), max_new_tokens=2)
    b = eng.submit(np.array([4, 5]), max_new_tokens=2)
    assert isinstance(a, Request) and (a.rid, b.rid) == (0, 1)
    c = eng.submit(np.array([6]), rid=7, max_new_tokens=2)
    assert c.rid == 7
    d = eng.submit(np.array([7]), max_new_tokens=2)
    assert d.rid == 8                          # auto-rids skip past explicit


def test_legacy_positional_submit_warns_and_works(lm):
    cfg, model, params = lm
    eng = Engine(model, params, batch_size=2, max_seq_len=16)
    with pytest.warns(DeprecationWarning, match="submit"):
        req = eng.submit(3, np.array([1, 2, 3]), max_new_tokens=2)
    assert req.rid == 3
    done = eng.run()
    assert done[3].out_tokens == req.out_tokens and len(req.out_tokens) == 2


def test_stream_yields_in_completion_order(lm):
    cfg, model, params = lm
    workload = _ragged_workload(cfg)
    eng = Engine(model, params, batch_size=2, max_seq_len=32)
    handles = [eng.submit(p, max_new_tokens=m) for p, m in workload]
    order = [r.rid for r in eng.stream()]
    assert sorted(order) == [h.rid for h in handles]
    assert all(h.done for h in handles)
    # short requests admitted early finish before long ones: completion
    # order is not submission order on a ragged workload
    assert order != [h.rid for h in handles]


def test_submit_validation_messages(lm):
    cfg, model, params = lm
    eng = Engine(model, params, batch_size=2, max_seq_len=16)
    with pytest.raises(ValueError, match="non-empty 1-D"):
        eng.submit(np.array([], np.int32))
    with pytest.raises(ValueError, match="max_seq_len=16"):
        eng.submit(np.arange(1, 17))
    with pytest.raises(ValueError, match="max_new_tokens"):
        eng.submit(np.array([1]), max_new_tokens=0)


# --------------------------------------------------------------------------
# shadow profiling + drift
# --------------------------------------------------------------------------

def test_shadow_serving_bit_identical_and_reports(lm):
    """Shadow-sampled requests serve the truncated lane's tokens — turning
    shadow profiling on changes observability, never the stream — and the
    per-request + rolling serving reports fill in."""
    cfg, model, params = lm
    pol = TruncationPolicy.scoped("**/mlp", "e5m7")
    workload = _ragged_workload(cfg)
    plain = Engine(model, params, batch_size=2, max_seq_len=32, policy=pol)
    ph = [plain.submit(p, max_new_tokens=m) for p, m in workload]
    plain.run()

    shadow = ShadowConfig(rate=1.0, threshold=1e-3)
    eng = Engine(model, params, batch_size=2, max_seq_len=32, policy=pol,
                 shadow=shadow)
    sh = [eng.submit(p, max_new_tokens=m) for p, m in workload]
    eng.run()
    assert all(h.shadowed for h in sh)
    assert [tuple(a.out_tokens) for a in sh] == \
           [tuple(a.out_tokens) for a in ph]
    assert eng.serving_report is not None
    assert eng.serving_report.top(1)           # merged rolling report
    assert all(h.report is not None for h in sh)
    sizes = eng.cache_sizes()
    assert sizes["shadow"] == 1 and sizes["reset"] == 1


def test_shadow_rate_zero_samples_nothing(lm):
    cfg, model, params = lm
    pol = TruncationPolicy.scoped("**/mlp", "e5m7")
    eng = Engine(model, params, batch_size=2, max_seq_len=16, policy=pol,
                 shadow=ShadowConfig(rate=0.0))
    h = eng.submit(np.array([1, 2, 3]), max_new_tokens=3)
    eng.run()
    assert not h.shadowed and h.report is None
    assert eng.serving_report is None or not eng.serving_report.top(1)


def test_drift_detection_pages_and_lands_in_provenance(lm):
    """A deployed artifact whose recorded budget the live traffic blows
    through fires exactly one drift event: hook called, blame ranked, and
    the guardrail log attached to the (new) artifact's provenance."""
    cfg, model, params = lm
    art = PolicyArtifact(name="drifty",
                         policy=TruncationPolicy.everywhere("e5m2"),
                         provenance={"threshold": 1e-7})
    events = []
    shadow = ShadowConfig(rate=1.0, threshold=1e-6, min_shadow_ticks=4,
                          drift_margin=4.0, on_drift=events.append)
    eng = Engine(model, params, batch_size=2, max_seq_len=32, policy=art,
                 shadow=shadow)
    for p, m in _ragged_workload(cfg):
        eng.submit(p, max_new_tokens=m)
    eng.run()
    assert len(events) == 1                    # latched: fires once
    ev = events[0]
    assert isinstance(ev, DriftEvent)
    assert ev.budget == pytest.approx(1e-7)
    assert ev.peak > 4.0 * ev.budget
    assert ev.blame and isinstance(ev.blame[0][0], str)
    assert eng.drift_events == [ev]
    kinds = eng.guardrail_log.kinds()
    assert kinds["drift_detected"] == 1 and kinds["research_paged"] == 1
    # the re-deployed artifact carries the evidence
    prov = eng.artifact.provenance["guardrail_log"]
    assert any(e["kind"] == "drift_detected" for e in prov)


def test_no_drift_within_budget(lm):
    cfg, model, params = lm
    # e8m16: its worst-element deviation on this model stays near 1e-2 for
    # any weight draw; e8m10's ranges 0.27-0.58 across draws, straddling
    # the 0.4 firing line, so it tested the draw and not the detector
    art = PolicyArtifact(name="stable",
                         policy=TruncationPolicy.scoped("**/mlp", "e8m16"),
                         provenance={"threshold": 1e-1})
    events = []
    eng = Engine(model, params, batch_size=2, max_seq_len=32, policy=art,
                 shadow=ShadowConfig(rate=1.0, threshold=1e-3,
                                     min_shadow_ticks=2,
                                     on_drift=events.append))
    for p, m in _ragged_workload(cfg, n=2):
        eng.submit(p, max_new_tokens=m)
    eng.run()
    assert events == [] and eng.drift_events == []


# --------------------------------------------------------------------------
# unified profiling surface: keyword-only tails + deprecation shims
# --------------------------------------------------------------------------

def _f(x):
    return jnp.sin(x) * x


def test_memtrace_positional_threshold_deprecated():
    pol = TruncationPolicy.everywhere("e5m2")
    x = jnp.linspace(0.1, 2.0, 8)
    with pytest.warns(DeprecationWarning, match="threshold"):
        legacy = memtrace(_f, pol, 1e-2)
    modern = memtrace(_f, pol, threshold=1e-2)
    out_l, rep_l = legacy(x)
    out_m, rep_m = modern(x)
    assert np.array_equal(np.asarray(out_l), np.asarray(out_m))
    assert rep_l.top(2) == rep_m.top(2)


def test_profile_trajectory_positional_threshold_deprecated():
    pol = TruncationPolicy.everywhere("e5m2")
    x = jnp.linspace(0.1, 2.0, 8)
    with pytest.warns(DeprecationWarning, match="threshold"):
        legacy = profile_trajectory(_f, pol, 1e-2, n_steps=3)
    modern = profile_trajectory(_f, pol, threshold=1e-2, n_steps=3)
    assert legacy(x)[1].totals.top(1) == modern(x)[1].totals.top(1)


def test_profile_counts_signature_cache():
    pol = TruncationPolicy.everywhere("e5m7")
    counts = profile_counts(_f, pol)
    x = jnp.linspace(0.1, 2.0, 8)
    r1 = counts(x)
    r2 = counts(x)
    assert r1 == r2
    assert counts.n_traces == 1 and counts.cache_size() == 1
    counts.cache_clear()
    assert counts.cache_size() == 0


# --------------------------------------------------------------------------
# shared policy resolution (core.policy.resolve_policy)
# --------------------------------------------------------------------------

def test_resolve_policy_flag_string():
    res = resolve_policy("scope:**/mlp=e5m7")
    assert isinstance(res, ResolvedPolicy)
    assert res.policy == parse_policy("scope:**/mlp=e5m7")
    assert res.artifact is None and res.ref is None


def test_resolve_policy_none_and_empty():
    assert resolve_policy(None) == ResolvedPolicy()
    assert resolve_policy("") == ResolvedPolicy()


def test_resolve_policy_exclusive():
    with pytest.raises(ValueError, match="exclusive"):
        resolve_policy("scope:**/mlp=e5m7", "name@v1")


def test_resolve_policy_passthrough():
    pol = TruncationPolicy.everywhere("e5m4")
    assert resolve_policy(pol).policy is pol
    art = PolicyArtifact(name="pt", policy=pol)
    res = resolve_policy(art)
    assert res.policy is art.policy and res.artifact is art


def test_resolve_policy_registry_ref(tmp_path):
    pol = TruncationPolicy.scoped("**/attn", "e8m7")
    reg = Registry(str(tmp_path))
    ref = reg.save(PolicyArtifact(name="served", policy=pol))
    res = resolve_policy(ref.ref, registry=str(tmp_path))
    assert res.policy == pol
    assert res.ref is not None and res.ref.name == "served"
    # artifact_ref argument form (launch flags)
    res2 = resolve_policy(None, ref.ref, registry=reg)
    assert res2.policy == pol and res2.ref == res.ref


def test_launch_serve_resolve_policy_wrapper(tmp_path):
    """launch.serve keeps its (policy, artifact) convenience wrapper but
    routes through the shared core resolver."""
    from repro.launch.serve import resolve_policy as serve_resolve
    pol, art = serve_resolve("scope:**/mlp=e5m7", None)
    assert art is None and pol == parse_policy("scope:**/mlp=e5m7")
    with pytest.raises(SystemExit):
        serve_resolve("scope:**/mlp=e5m7", "x@v1")
