"""Mini-app protocol + profiling-stack integration (fast tier-1 slice).

Small app configurations so every transform stays cheap; the full-size
FP64-oracle acceptance runs in the conformance tier
(tests/conformance/test_apps_e2e.py).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import search
from repro.apps import (
    APPS, get_app, observable_error, HeatDiffusion, PoissonCG, SodShockTube,
    oracle,
)
from repro.core import (
    truncate, truncate_sweep, memtrace, profile_counts, TruncationPolicy,
)

SMALL = {
    "sod": dict(n_cells=32, t_end=0.04),
    "heat": dict(n=8, n_explicit=8, n_implicit=1, cg_iters=6),
    "poisson": dict(n=8, cg_iters=12),
}


def small_app(name):
    return get_app(name, **SMALL[name])


@pytest.mark.parametrize("name", sorted(APPS))
def test_protocol_surface(name):
    """init_state honors dtype; run_observables returns the documented dict;
    the self-metric is exactly zero; scopes are discoverable in the jaxpr."""
    app = small_app(name)
    state = app.init_state(jnp.float32)
    for leaf in jax.tree_util.tree_leaves(state):
        assert leaf.dtype == jnp.float32
    obs = app.run_observables(state)
    assert isinstance(obs, dict) and obs
    assert app.error_metric(obs, obs) == 0.0
    scopes = app.default_policy_scopes()
    assert scopes
    closed = jax.make_jaxpr(app.run_observables)(state)
    tree = search.scope_tree(closed)
    for s in scopes:
        assert any(path == s or path.startswith(s + "/") for path in tree), \
            (s, sorted(tree))


@pytest.mark.parametrize("name", sorted(APPS))
def test_init_state_f64_starts_from_f32_bits(name):
    """The oracle contract: f64 initial data carries exactly the f32-rounded
    values, so trajectories differ by solver arithmetic only."""
    app = small_app(name)
    s32 = app.init_state(jnp.float32)
    with jax.enable_x64(True):
        s64 = app.init_state(jnp.float64)
        for a, b in zip(jax.tree_util.tree_leaves(s32),
                        jax.tree_util.tree_leaves(s64)):
            assert b.dtype == jnp.float64
            assert np.array_equal(np.asarray(a, np.float64), np.asarray(b))


@pytest.mark.parametrize("name", sorted(APPS))
def test_truncate_fine_format_stays_accurate(name):
    app = small_app(name)
    state = app.init_state(jnp.float32)
    obs = app.run_observables(state)
    lossy = truncate(app.run_observables, app.uniform_policy("e8m15"))(state)
    err = app.error_metric(obs, lossy)
    assert np.isfinite(err) and err < 1e-2, err


@pytest.mark.parametrize("name", sorted(APPS))
def test_truncate_sweep_matches_truncate(name):
    """The runtime-table sweep path equals per-policy truncate bit-for-bit
    on each app (the small-config slice of the conformance parity test)."""
    app = small_app(name)
    state = app.init_state(jnp.float32)
    sites = TruncationPolicy(rules=tuple(
        search.driver.TruncationRule(fmt=search.driver.FPFormat(8, 0),
                                     scope=s)
        for s in app.default_policy_scopes()))
    handle = truncate_sweep(app.run_observables, sites)(state)
    pol = app.uniform_policy("e8m5")
    swept = handle(handle.table(pol))
    direct = truncate(app.run_observables, pol)(state)
    for a, b in zip(jax.tree_util.tree_leaves(swept),
                    jax.tree_util.tree_leaves(direct)):
        an = np.asarray(jax.device_get(a))
        bn = np.asarray(jax.device_get(b))
        assert np.array_equal(an.view(np.uint32), bn.view(np.uint32))


def test_memtrace_flags_on_sod():
    app = small_app("sod")
    state = app.init_state(jnp.float32)
    _out, rep = memtrace(app.run_observables, app.uniform_policy("e8m2"),
                         threshold=1e-3)(state)
    assert int(np.sum(np.asarray(jax.device_get(rep.flags)))) > 0
    assert "hydro" in " ".join(rep.locations)


def test_profile_counts_on_heat():
    app = small_app("heat")
    state = app.init_state(jnp.float32)
    rep = profile_counts(app.run_observables, app.uniform_policy())(state)
    # the solver scopes carry truncated work; the harness (observables)
    # must not be matched by the scoped policy
    assert 0.0 < rep.truncated_fraction < 1.0


def test_autosearch_smoke_on_poisson():
    """A tiny-budget search on the smallest app converges and round-trips
    through the public truncate API."""
    app = small_app("poisson")
    state = app.init_state(jnp.float32)
    res = search.autosearch(app.run_observables, (state,),
                            metric=app.error_metric, budget=16,
                            threshold=5e-2)
    assert res.converged, res.table()
    obs = truncate(app.run_observables, res.policy())(state)
    assert app.error_metric(app.run_observables(state), obs) <= 5e-2


def test_oracle_verdict_small_heat():
    """FP64 reference wiring: the plain f32 run must pass its budget with a
    tiny floor, and the verdict renders."""
    app = small_app("heat")
    ref = oracle.fp64_reference(app)
    assert all(v.dtype == np.float64 for v in ref.values())
    v = oracle.verdict(app, oracle.fp32_observables(app), ref)
    assert v.passed and v.error == v.floor
    assert "PASS" in str(v)


def test_observable_error_edges():
    a = {"x": jnp.float32(2.0), "f": jnp.ones((4,), jnp.float32)}
    assert observable_error(a, a) == 0.0
    bad = {"x": jnp.float32(jnp.nan), "f": jnp.ones((4,), jnp.float32)}
    assert observable_error(a, bad) == float("inf")
    with pytest.raises(ValueError):
        observable_error(a, {"x": jnp.float32(1.0)})


def test_get_app_registry():
    assert sorted(APPS) == ["heat", "poisson", "sod"]
    assert isinstance(get_app("sod", n_cells=16), SodShockTube)
    assert isinstance(get_app("heat"), HeatDiffusion)
    assert isinstance(get_app("poisson"), PoissonCG)
    with pytest.raises(ValueError):
        get_app("navier-stokes")


def test_metric_resolution():
    """Satellite contract: autosearch metrics resolve from None (historical
    max_rel), names, and callables; from_observables lifts state-space
    outputs to observable space."""
    assert search.resolve_metric(None) is search.rel_error
    assert search.resolve_metric("max_rel") is search.rel_error
    assert search.resolve_metric("rel_l2") is search.rel_l2_error
    fn = lambda r, c: 0.123  # noqa: E731
    assert search.resolve_metric(fn) is fn
    with pytest.raises(ValueError):
        search.resolve_metric("nope")
    with pytest.raises(TypeError):
        search.resolve_metric(42)

    lifted = search.from_observables(lambda out: {"m": jnp.sum(out)},
                                     "rel")
    a = jnp.asarray([1.0, 2.0], jnp.float32)
    assert lifted(a, a) == 0.0
    assert lifted(a, a * 2) == pytest.approx(1.0)


def test_rel_l2_error_metric():
    r = np.asarray([3.0, 4.0], np.float32)
    assert search.rel_l2_error(r, r) == 0.0
    assert search.rel_l2_error(r, np.asarray([3.0, 5.0], np.float32)) == \
        pytest.approx(1.0 / 5.0)
    assert search.rel_l2_error(r, np.asarray([np.nan, 4.0], np.float32)) \
        == float("inf")
