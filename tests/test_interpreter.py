"""Op-mode interpreter: HOP coverage, scoping, policies, grad composition."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import (
    truncate, profile_counts, TruncationPolicy, TruncationRule,
    E5M2, BF16, magnitude_below, scope,
)
from repro.kernels.quantize_em.ops import quantize


def quant(x, fmt=E5M2):
    return quantize(jnp.asarray(x, jnp.float32), fmt, impl="ref")


def test_identity_policy_is_exact():
    def f(x):
        return jnp.sum(jnp.sin(x * 3) ** 2)
    x = jnp.asarray(np.random.RandomState(0).randn(16), jnp.float32)
    pol = TruncationPolicy.everywhere("fp32")
    assert float(truncate(f, pol)(x)) == float(f(x))


def test_single_op_semantics():
    """One multiply: truncate(f) == quantize(f) exactly."""
    def f(a, b):
        return a * b
    a = jnp.float32(1.234567)
    b = jnp.float32(7.654321)
    pol = TruncationPolicy.everywhere(E5M2)
    got = truncate(f, pol)(a, b)
    want = quant(a * b)
    assert float(got) == float(want)


def test_chained_op_semantics():
    """Each intermediate is rounded: ((a*b)_q + c)_q."""
    def f(a, b, c):
        return a * b + c
    a, b, c = map(jnp.float32, (1.7, 2.9, 0.111))
    pol = TruncationPolicy.everywhere(E5M2)
    got = truncate(f, pol)(a, b, c)
    want = quant(quant(a * b) + c)
    assert float(got) == float(want)


def test_scope_matching_through_scan():
    def f(x):
        with scope("inner"):
            def body(c, _):
                return jnp.sin(c * 1.01), None
            y, _ = lax.scan(body, x, None, length=4)
        return jnp.sum(y)
    x = jnp.asarray(np.random.RandomState(1).randn(8), jnp.float32)
    full = float(f(x))
    hit = float(truncate(f, TruncationPolicy.scoped("inner", E5M2))(x))
    miss = float(truncate(f, TruncationPolicy.scoped("elsewhere", E5M2))(x))
    assert hit != full
    assert miss == full


def test_while_and_cond():
    def f(x):
        y = lax.while_loop(lambda v: jnp.sum(v) < 100.0,
                           lambda v: v * 1.5 + 0.01, x)
        return lax.cond(jnp.sum(y) > 50, lambda a: a * 2.0,
                        lambda a: a / 2.0, y).sum()
    x = jnp.ones((4,), jnp.float32)
    full = float(f(x))
    tr = float(truncate(f, TruncationPolicy.everywhere(E5M2))(x))
    assert np.isfinite(tr) and tr != full


def test_remat_preserved():
    def f(x):
        return jnp.sum(jax.checkpoint(lambda v: jnp.tanh(v * 3))(x) ** 2)
    x = jnp.asarray(np.random.RandomState(2).randn(8), jnp.float32)
    pol = TruncationPolicy.everywhere(E5M2)
    tr = truncate(f, pol)
    v = float(tr(x))
    g = jax.grad(lambda v_: truncate(f, pol)(v_))(x)
    assert np.isfinite(v) and bool(jnp.all(jnp.isfinite(g)))


def test_custom_jvp_primal():
    @jax.custom_jvp
    def h(x):
        return jnp.sin(x)
    h.defjvp(lambda p, t: (jnp.sin(p[0]), jnp.cos(p[0]) * t[0]))

    def f(x):
        return jnp.sum(h(x * 2))
    x = jnp.asarray(np.random.RandomState(3).randn(8), jnp.float32)
    tr = float(truncate(f, TruncationPolicy.everywhere(E5M2))(x))
    assert np.isfinite(tr) and tr != float(f(x))


def test_grad_then_truncate_covers_backward():
    def loss(w):
        return jnp.sum(jnp.tanh(w) ** 2)
    w = jnp.asarray(np.random.RandomState(4).randn(16), jnp.float32)
    g_full = jax.grad(loss)(w)
    g_tr = truncate(jax.grad(loss), TruncationPolicy.everywhere(E5M2))(w)
    assert not np.allclose(np.asarray(g_full), np.asarray(g_tr))
    # every surviving value lies on the e5m2 grid
    q = quantize(g_tr, E5M2, impl="ref")
    np.testing.assert_array_equal(np.asarray(q), np.asarray(g_tr))


def test_exclusion_fences_region():
    def f(x):
        with scope("a"):
            y = x * 1.1
        with scope("b"):
            z = y * 1.1
        return jnp.sum(y + z)
    x = jnp.asarray(np.random.RandomState(5).randn(8), jnp.float32)
    pol = TruncationPolicy.everywhere(E5M2)
    fenced = float(truncate(f, pol.excluding("a", "b"))(x))
    # with a and b fenced, only the unscoped add + reduce_sum are truncated
    y = x * jnp.float32(1.1)
    z = y * jnp.float32(1.1)
    want = float(quant(jnp.sum(quant(y + z)).astype(jnp.float32)))
    assert fenced == want


def test_from_width_rule():
    def f(x32):
        return jnp.sum(x32 * 1.01)
    x = jnp.asarray(np.random.RandomState(6).randn(8), jnp.float32)
    pol = TruncationPolicy.from_flag("64_to_5_10")   # no f64 ops present
    # against the jitted plain function: eager op-by-op dispatch rounds the
    # unfused multiply+sum differently from any compiled program
    plain = float(jax.jit(f)(x))
    assert float(truncate(f, pol)(x)) == plain
    pol32 = TruncationPolicy.from_flag("32_to_5_2")
    assert float(truncate(f, pol32)(x)) != plain


def test_dynamic_mask_truncation():
    """AMR analogue: truncate only small-magnitude elements."""
    def f(x):
        return x * 1.0000001
    x = jnp.asarray([1e-4, 100.0], jnp.float32)
    rule = TruncationRule(fmt=E5M2, mask=magnitude_below(1.0))
    pol = TruncationPolicy(rules=(rule,))
    y = np.asarray(truncate(f, pol)(x))
    raw = np.asarray(f(x))
    # large element untouched by the mask, small element on the e5m2 grid
    assert y[1] == raw[1]
    q = np.asarray(quant(raw[0]))
    assert y[0] == q and y[0] != raw[0]


def test_dot_input_quantization():
    a = jnp.asarray(np.random.RandomState(7).randn(8, 8), jnp.float32)
    b = jnp.asarray(np.random.RandomState(8).randn(8, 8), jnp.float32)

    def f(a, b):
        return jnp.sum(a @ b)
    rule_in = TruncationRule(fmt=BF16, quantize_dot_inputs=True)
    pol_in = TruncationPolicy(rules=(rule_in,))
    got = float(truncate(f, pol_in)(a, b))
    want = float(jnp.sum(quantize(a, BF16) @ quantize(b, BF16)))
    # the final reduce-sum is itself quantized too; compare via quantize
    assert abs(got - float(quant(jnp.float32(want), BF16))) < 1e-3


def test_counters_scan_multiplier():
    def f(x):
        def body(c, _):
            return c @ c, None
        y, _ = lax.scan(body, x, None, length=5)
        return y
    x = jnp.eye(8, dtype=jnp.float32)
    rep = profile_counts(f, TruncationPolicy.everywhere(E5M2))(x)
    # 5 iterations x (2 * 8^3) flops
    assert rep.total_flops == pytest.approx(5 * 2 * 8 ** 3)
    assert rep.truncated_fraction == pytest.approx(1.0)


def test_scoped_policy_survives_grad():
    """Backward-pass ops keep their forward scope after normalization
    (jvp()/transpose() wrappers must not break RAPTOR scoping)."""
    def loss(w, x):
        with scope("mlp"):
            h = jnp.tanh(x @ w)
        return jnp.sum(h ** 2)
    w = jnp.asarray(np.random.RandomState(0).randn(8, 8), jnp.float32)
    x = jnp.asarray(np.random.RandomState(1).randn(4, 8), jnp.float32)
    # jitted, like truncate's own output: eager dispatch rounds differently
    g_full = jax.jit(jax.grad(loss))(w, x)
    g_tr = truncate(jax.grad(loss), TruncationPolicy.scoped("mlp", E5M2))(w, x)
    assert not np.allclose(np.asarray(g_full), np.asarray(g_tr))
    g_miss = truncate(jax.grad(loss),
                      TruncationPolicy.scoped("nothing", E5M2))(w, x)
    np.testing.assert_array_equal(np.asarray(g_full), np.asarray(g_miss))


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs nested in it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for p in eqn.params.values():
            for sub in (p if isinstance(p, (tuple, list)) else (p,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner)


@pytest.mark.parametrize("transform", ["truncate", "memtrace"])
def test_rebinding_keeps_scopes_and_kernel_names(transform):
    """Each equation is rebound under its own name stack: ops keep their
    scopes (inside a scan too), and a Pallas kernel, whose compiled op is
    named after its innermost scope, keeps its name."""
    from repro.core import memtrace
    from repro.kernels import kv_write

    def f(x, k, rows):
        with jax.named_scope("outer"):
            def body(c, xi):
                with jax.named_scope("mlp"):
                    return c + xi * 2.0, None
            y, _ = lax.scan(body, x[0], x)
        k, _ = kv_write.kv_write(k, k, rows, rows, jnp.zeros((1,), jnp.int32),
                                 interpret=True)
        return y, k

    pol = TruncationPolicy.scoped("**/mlp", E5M2)
    args = (jnp.ones((3, 4), jnp.float32), jnp.zeros((1, 1, 1, 8, 128)),
            jnp.ones((1, 1, 1, 8)))
    fn = truncate(f, pol) if transform == "truncate" else \
        memtrace(f, pol, threshold=1e-3)
    stacks = {(e.primitive.name, str(e.source_info.name_stack))
              for e in _eqns(jax.make_jaxpr(fn)(*args).jaxpr)}
    assert any(p == "pallas_call" and s.endswith("kv_write")
               for p, s in stacks), stacks
    assert any(p == "mul" and s.endswith("mlp") for p, s in stacks), stacks
    assert any(p == "scan" and s.endswith("outer") for p, s in stacks), stacks
