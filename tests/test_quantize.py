"""Quantizer correctness: ml_dtypes oracles, Pallas kernel sweeps, and
hypothesis property tests on the (e,m)-grid invariants."""
import ml_dtypes
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import jax
import jax.numpy as jnp

from repro.core.formats import (
    FPFormat, parse_format, BF16, FP16, E5M2, E4M3, E4M3FN,
)
from repro.kernels.quantize_em.ops import quantize
from repro.kernels.quantize_em.ref import quantize_ref_fmt


def _test_vector(n=4096, seed=0):
    rng = np.random.RandomState(seed)
    x = np.concatenate([
        rng.randn(n).astype(np.float32)
        * 10 ** rng.uniform(-12, 12, n).astype(np.float32),
        np.array([0.0, -0.0, 1.0, -1.0, np.inf, -np.inf, np.nan,
                  65504.0, 65505.0, 448.0, 464.0, 480.0, 3e-5,
                  5.96e-8, 2.98e-8, 1e-45, -1e-45, 2 ** -126, 2 ** -133],
                 np.float32)])
    return x.astype(np.float32)


HW = [(BF16, ml_dtypes.bfloat16), (FP16, np.float16),
      (E5M2, None), (E4M3FN, ml_dtypes.float8_e4m3fn)]


@pytest.mark.parametrize("impl", ["ref", "interpret"])
@pytest.mark.parametrize("fmt,mld", [
    (BF16, ml_dtypes.bfloat16), (FP16, np.float16),
    (E5M2, ml_dtypes.float8_e5m2), (E4M3FN, ml_dtypes.float8_e4m3fn)])
def test_matches_ml_dtypes(fmt, mld, impl):
    x = _test_vector()
    ours = np.asarray(quantize(jnp.asarray(x), fmt, impl=impl))
    with np.errstate(over="ignore"):
        theirs = x.astype(mld).astype(np.float32)
    same = ((ours == theirs) | (np.isnan(ours) & np.isnan(theirs))
            | ((ours == 0) & (theirs == 0)))
    # documented convention difference: we pass inf through even for fn
    # layouts (profiling wants the overflow signal); ml_dtypes maps inf->nan
    same |= np.isinf(x)
    bad = np.where(~same)[0]
    assert len(bad) == 0, [(x[i], ours[i], theirs[i]) for i in bad[:5]]


@pytest.mark.parametrize("e,m", [(5, 14), (3, 8), (8, 3), (2, 1), (6, 20),
                                 (4, 0), (5, 2), (8, 23)])
@pytest.mark.parametrize("shape", [(7,), (128,), (33, 65), (2, 3, 129)])
def test_pallas_matches_ref_sweep(e, m, shape):
    rng = np.random.RandomState(e * 100 + m)
    x = jnp.asarray(rng.randn(*shape) * 10 ** rng.uniform(-8, 8, shape),
                    jnp.float32)
    fmt = FPFormat(e, m)
    a = quantize(x, fmt, impl="ref")
    b = quantize(x, fmt, impl="interpret")
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float16, jnp.float32])
def test_dtype_roundtrip(dtype):
    x = jnp.asarray(np.random.RandomState(0).randn(256), dtype)
    y = quantize(x, FPFormat(5, 2), impl="ref")
    assert y.dtype == x.dtype


def test_f64_carrier():
    with jax.enable_x64(True):
        # genuine f64 values (not f32-exact upcasts)
        x64 = jnp.asarray(np.random.RandomState(0).randn(64).astype(np.float64)
                          / 3.0)
        y = quantize(x64, FPFormat(8, 30), impl="ref")
        assert y.dtype == jnp.float64
        # m=30: coarser than the f64 inputs, finer than f32
        assert not np.array_equal(np.asarray(y), np.asarray(x64))
        assert not np.array_equal(np.asarray(y),
                                  np.asarray(x64.astype(jnp.float32)
                                             .astype(jnp.float64)))
        # RAPTOR's original use case: 64_to_5_14 style truncation
        z = quantize(x64, parse_format("5_14"), impl="ref")
        q2 = quantize(z, parse_format("5_14"), impl="ref")
        np.testing.assert_array_equal(np.asarray(z), np.asarray(q2))


# ---- hypothesis property tests ---------------------------------------------

fmts = st.tuples(st.integers(2, 8), st.integers(0, 20)).map(
    lambda em: FPFormat(*em))
floats = st.floats(allow_nan=False, allow_infinity=False, width=32)


@given(fmt=fmts, xs=st.lists(floats, min_size=1, max_size=32))
@settings(max_examples=200, deadline=None)
def test_idempotent(fmt, xs):
    x = jnp.asarray(np.asarray(xs, np.float32))
    q1 = quantize(x, fmt, impl="ref")
    q2 = quantize(q1, fmt, impl="ref")
    np.testing.assert_array_equal(np.asarray(q1), np.asarray(q2))


@given(fmt=fmts, xs=st.lists(floats, min_size=2, max_size=32))
@settings(max_examples=200, deadline=None)
def test_monotone(fmt, xs):
    """x <= y implies q(x) <= q(y) — rounding preserves order."""
    x = np.sort(np.asarray(xs, np.float32))
    q = np.asarray(quantize(jnp.asarray(x), fmt, impl="ref"))
    finite = np.isfinite(q)
    qq = q[finite]
    assert np.all(np.diff(qq) >= 0)


@given(fmt=fmts, x=floats)
@settings(max_examples=300, deadline=None)
def test_error_bound(fmt, x):
    """|q(x) - x| <= max(ulp/2, sub_scale/2) within the finite range."""
    xa = np.float32(x)
    if abs(float(xa)) > fmt.max_finite:
        return
    q = float(np.asarray(quantize(jnp.asarray([xa]), fmt, impl="ref"))[0])
    if abs(float(xa)) < fmt.min_normal:
        tol = fmt.min_subnormal / 2
    else:
        import math
        e = math.floor(math.log2(abs(float(xa)))) if xa != 0 else fmt.min_exp
        tol = 2.0 ** (e - fmt.man_bits) / 2 * 1.0000001
    assert abs(q - float(xa)) <= tol, (float(xa), q, tol)


@given(fmt=fmts, x=floats)
@settings(max_examples=200, deadline=None)
def test_sign_preserved(fmt, x):
    xa = np.float32(x)
    q = float(np.asarray(quantize(jnp.asarray([xa]), fmt, impl="ref"))[0])
    if q != 0 and np.isfinite(q):
        assert np.sign(q) == np.sign(xa)


def test_ties_to_even():
    # e4m3 (ieee): grid step at [1,2) is 1/8; midpoints round to even mantissa
    fmt = FPFormat(4, 3)
    x = jnp.asarray([1.0625, 1.1875], jnp.float32)   # midpoints
    q = np.asarray(quantize(x, fmt, impl="ref"))
    np.testing.assert_allclose(q, [1.0, 1.25])        # both to even


def test_identity_fast_path():
    x = jnp.asarray(np.random.RandomState(0).randn(64), jnp.float32)
    y = quantize(x, parse_format("fp32"))
    assert y is x  # no-op object identity


def test_raptor_flag_formats():
    f = parse_format("5_14")
    assert (f.exp_bits, f.man_bits) == (5, 14)
    f2 = parse_format("e6m9s")
    assert f2.saturate and f2.man_bits == 9
