"""Runtime-parameterized sweeps: `truncate_sweep` must reproduce `truncate`
exactly from format tables, evaluate whole candidate ladders through ONE
compiled executable (no per-candidate retrace/recompile), and the batched
`autosearch` must stay within an O(1) XLA-compilation budget."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from repro import search
from repro.core import (
    truncate, truncate_sweep, TruncationPolicy, TruncationRule, scope,
)
from repro.core import policy as policy_mod
from repro.core.policy import magnitude_below
from repro.launch.compilation import count_compiles



def _toy(w1, w2, x):
    with scope("attn"):
        h = jnp.tanh(x @ w1)
    with scope("mlp"):
        def body(c, _):
            return jax.nn.relu(c @ w2), None
        h, _ = lax.scan(body, h, None, length=3)
    with scope("head"):
        return jnp.mean(h * h)


def _toy_args(seed=0):
    r = np.random.RandomState(seed)
    return (jnp.asarray(r.randn(32, 64) / 8, jnp.float32),
            jnp.asarray(r.randn(64, 64) / 8, jnp.float32),
            jnp.asarray(r.randn(16, 32), jnp.float32))


_POLICIES = [
    TruncationPolicy.everywhere("e5m2"),
    TruncationPolicy.scoped("mlp", "e8m7"),
    TruncationPolicy.scoped("attn", "e4m3"),
    TruncationPolicy.everywhere("e5m7").excluding("mlp"),
    TruncationPolicy(rules=(TruncationRule(fmt="e8m3", scope="attn"),
                            TruncationRule(fmt="e5m2", scope="head"))),
    TruncationPolicy(rules=()),
]


def test_table_eval_matches_truncate_exactly():
    """Any policy within the site set, lowered to a table, must produce the
    same bits as the static per-policy transform (incl. scan bodies and
    excludes)."""
    args = _toy_args()
    handle = truncate_sweep(_toy, TruncationPolicy.everywhere("e5m2"))(*args)
    assert handle.num_sites >= 4
    for pol in _POLICIES:
        a = float(truncate(_toy, pol)(*args))
        b = float(handle(handle.table(pol)))
        assert a == b, pol


def test_batch_matches_single_rows():
    args = _toy_args()
    handle = truncate_sweep(_toy, TruncationPolicy.everywhere("e5m2"))(*args)
    tables = handle.tables(_POLICIES)
    outs = handle.batch(tables)
    for i in range(len(_POLICIES)):
        assert float(outs[i]) == float(handle(tables[i]))


def test_sweep_walks_jaxpr_once():
    args = _toy_args()
    sw = truncate_sweep(_toy, TruncationPolicy.everywhere("e5m2"))
    h1 = sw(*args)
    for pol in _POLICIES:
        h1(h1.table(pol))
    h2 = sw(*args)  # same signature -> cached sites/executable
    h2.batch(h2.tables(_POLICIES))
    assert sw.n_traces == 1
    assert sw.cache_size() == 1
    # a new input signature is a new walk, exactly one
    sw(*_toy_args()[:2], _toy_args()[2][:8])
    assert sw.n_traces == 2


def test_shared_subjaxpr_sites_are_per_call_site():
    """jax's tracing cache shares one ClosedJaxpr object between call sites
    of the same jitted helper; each call site's scope must still get its own
    quantize sites (regression: id()-keyed enumeration let the first call
    site's rows shadow every other, so scoped policies quantized the wrong
    scope)."""
    helper = jax.jit(lambda v: jnp.sin(v) * 1.5)

    def f(x):
        with scope("a"):
            y = helper(x)
        with scope("b"):
            z = helper(x + 1.0)
        return jnp.sum(y) + jnp.sum(z)

    x = jnp.asarray(np.random.RandomState(0).randn(16, 8), jnp.float32)
    handle = truncate_sweep(f, TruncationPolicy.everywhere("e5m2"))(x)
    for pol in (TruncationPolicy.scoped("a", "e5m2"),
                TruncationPolicy.scoped("b", "e5m2"),
                TruncationPolicy.everywhere("e5m2")):
        assert float(handle(handle.table(pol))) == float(truncate(f, pol)(x)), pol
    # and the two scoped policies genuinely differ from full precision
    full = float(f(x))
    assert float(handle(handle.table(TruncationPolicy.scoped("a", "e5m2")))) != full
    assert float(handle(handle.table(TruncationPolicy.scoped("b", "e5m2")))) != full


def test_closure_captured_tracer_rejected_not_cached():
    """A closure that captures a value from an enclosing trace must raise —
    and must NOT poison the signature cache for later concrete calls
    (regression: the entry was cached and every subsequent call died with
    UnexpectedTracerError)."""
    args = _toy_args()
    site_pol = TruncationPolicy.everywhere("e5m2")
    sw = truncate_sweep(_toy, site_pol)

    def inside(t):
        # tracer in the input leaves
        with pytest.raises(TypeError):
            sw(args[0] * t, args[1], args[2])
        # concrete leaves, but the traced fn closes over the tracer
        scaled = lambda w1, w2, x: _toy(w1 * t, w2, x)
        with pytest.raises(TypeError):
            truncate_sweep(scaled, site_pol)(*args)
        return t

    jax.jit(inside)(jnp.float32(1.0))
    assert sw.cache_size() == 0
    handle = sw(*args)  # same signature, now concrete: must work
    assert float(handle(handle.identity_table())) == float(_toy(*args))


def test_site_policy_rejects_runtime_unrepresentable_rules():
    args = _toy_args()
    masked = TruncationPolicy(rules=(
        TruncationRule(fmt="e5m2", mask=magnitude_below(1.0)),))
    with pytest.raises(ValueError):
        truncate_sweep(_toy, masked)(*args)
    handle = truncate_sweep(_toy, TruncationPolicy.everywhere("e5m2"))(*args)
    with pytest.raises(ValueError):
        handle.table(masked)


def test_policy_ladder_single_compile():
    """The tentpole guarantee at executable level: N candidate policies
    through one sweep handle cost ONE XLA compilation (static `truncate`
    would cost N)."""
    args = _toy_args()
    handle = truncate_sweep(_toy, TruncationPolicy.everywhere("e5m2"))(*args)
    tables = handle.tables(_POLICIES)
    with count_compiles() as n:
        for i in range(len(_POLICIES)):
            handle(tables[i])
    assert n[0] == 1, f"per-candidate recompile detected ({n[0]} compiles)"
    with count_compiles() as n:
        handle.batch(tables)
        handle.batch(handle.tables(_POLICIES[::-1]))  # same K -> same exe
    assert n[0] == 1, f"batched sweep recompiled ({n[0]} compiles)"


def test_autosearch_compile_budget_toy():
    """CI compile-count regression: the batched search must not recompile
    per candidate — one batched executable serves the whole run."""
    args = _toy_args()
    with count_compiles() as n:
        res = search.autosearch(_toy, args, search.rel_error, 32,
                                threshold=1e-2)
    assert res.converged
    assert res.evals_used > 2  # plenty of candidates were actually evaluated
    assert n[0] <= 2, f"search compiled {n[0]} executables"
    assert res.n_compiles <= 2


@pytest.mark.slow
def test_autosearch_compile_budget_bench_model():
    """Acceptance: autosearch on benchmarks.common.bench_model performs at
    most 2 XLA compilations total (down from O(scopes × widths))."""
    from benchmarks.common import bench_model, bench_batch

    cfg, model, params = bench_model()
    batch = bench_batch(cfg)
    with count_compiles() as n:
        res = search.autosearch(model.loss, (params, batch),
                                search.loss_degradation, 48, threshold=5e-3)
    assert n[0] <= 2, f"search compiled {n[0]} executables"
    assert res.converged, res.table()
    assert res.evals_used <= 48
    assert len(res.policy().rules) >= 1


# --------------------------------------------------------------------------
# interpreter matcher fast path (satellite): policies that cannot match
# anything must not pay per-equation matcher calls, and repeated triples
# must hit the precompiled-matcher memo
# --------------------------------------------------------------------------

def test_empty_policy_skips_matcher_entirely():
    args = _toy_args()
    empty = TruncationPolicy(rules=())
    tr = truncate(_toy, empty, cache=False)
    before = policy_mod.MATCHER_EVALS
    tr(*args)
    assert policy_mod.MATCHER_EVALS == before, \
        "empty policy paid per-equation matcher calls"


def test_matcher_memo_evaluates_each_triple_once():
    pol = TruncationPolicy.scoped("mlp", "e5m2")
    before = policy_mod.MATCHER_EVALS
    r1 = pol.rule_for("mlp/dot", "dot_general", np.dtype("float32"))
    mid = policy_mod.MATCHER_EVALS
    r2 = pol.rule_for("mlp/dot", "dot_general", np.dtype("float32"))
    assert mid == before + 1
    assert policy_mod.MATCHER_EVALS == mid  # memo hit, no re-evaluation
    assert r1 is r2 is pol.rules[0]


def test_matcher_memo_bounded_by_distinct_triples():
    """Re-walking the same jaxpr (cache=False forces per-call walks) must
    not re-run the matcher: the policy memo serves every repeat triple."""
    args = _toy_args()
    pol = TruncationPolicy.everywhere("e5m2")
    tr = truncate(_toy, pol, cache=False)
    tr(*args)
    after_first = policy_mod.MATCHER_EVALS
    tr(*args)
    assert policy_mod.MATCHER_EVALS == after_first
