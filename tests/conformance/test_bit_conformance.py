"""Bit-level conformance of the quantizer against an independent oracle.

Three mutually checking implementations must agree bit-for-bit on the
entire float16 value space (all 65536 patterns — every normal, subnormal,
±0, ±inf and NaN payload, exactly widened to f32):

  * ``quantize_dynamic(impl='ref')`` — the runtime-parameterized jnp path
                                 the whole sweep/search stack runs on,
  * the Pallas kernel          — ``quantize_dynamic(impl='interpret')``,
  * ``bit_oracle``             — exact-integer RNE, no shared code,

plus the static trace-time path (``quantize``) and, where a hardware cast
exists, ``ml_dtypes``. Randomized (e, m, saturate, ieee_inf) corners extend
the same contract across the full format space on adversarial values
(overflow boundaries, subnormal ties, half-way points). Any mismatch dumps
a bit-exact reproducer artifact (see ``harness.py``).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import repro.core  # noqa: F401  — import order: core before kernels
from repro.core.formats import FPFormat
from repro.kernels.quantize_em.ops import quantize, quantize_dynamic, \
    format_row
from bit_oracle import all_float16_values, format_constants, oracle_quantize
from harness import assert_bits_equal

pytestmark = pytest.mark.conformance

# (exp_bits, man_bits, saturate, ieee_inf): every hardware format, several
# search-ladder rungs, both overflow conventions, and range extremes
EXHAUSTIVE_FORMATS = [
    (5, 10, 0, 1),   # fp16 (the input grid itself: must be identity)
    (5, 2, 0, 1),    # e5m2
    (4, 3, 1, 0),    # e4m3 (saturating OCP)
    (4, 3, 0, 0),    # e4m3fn (NaN-overflow OCP)
    (8, 7, 0, 1),    # bf16
    (8, 10, 0, 1),   # tf32 rung
    (8, 5, 0, 1),    # ladder rung
    (8, 3, 0, 1),    # ladder rung
    (8, 23, 0, 1),   # carrier-fine: exact identity via the in-kernel gate
    (5, 14, 0, 1),   # RAPTOR's 5_14
    (3, 4, 0, 1),    # narrow-range ieee
    (2, 1, 1, 1),    # extreme narrow, saturating
    (6, 9, 1, 1),    # mid-range saturating
    (1, 5, 0, 1),    # degenerate exponent range
]


def _fmt_id(f):
    e, m, s, i = f
    return f"e{e}m{m}{'s' if s else ''}{'' if i else 'fn'}"


def _dyn(x, e, m, s, i, impl="ref"):
    row = np.array([e, m, s, i], np.int32)
    return np.asarray(jax.device_get(
        quantize_dynamic(jnp.asarray(x), row, impl=impl)))


@pytest.fixture(scope="module")
def f16_space():
    return all_float16_values()


@pytest.mark.parametrize("fmt", EXHAUSTIVE_FORMATS, ids=_fmt_id)
def test_exhaustive_fp16_dynamic_vs_oracle(fmt, f16_space):
    """The runtime-parameterized quantizer agrees with the exact-integer
    oracle on every float16 bit pattern."""
    e, m, s, i = fmt
    got = _dyn(f16_space, e, m, s, i)
    want = oracle_quantize(f16_space, e, m, bool(s), bool(i))
    assert_bits_equal(f"dynamic-vs-oracle-{_fmt_id(fmt)}",
                      f16_space, got, want, fmt=fmt)


@pytest.mark.parametrize("fmt", EXHAUSTIVE_FORMATS, ids=_fmt_id)
def test_exhaustive_fp16_three_way_parity(fmt, f16_space):
    """static trace-time path == dynamic jnp path == Pallas kernel
    (interpret mode), bit for bit, over the whole fp16 space. The static
    leg is NaN-payload-free: for bf16/fp16 it lowers to a hardware
    ``astype`` pair, which canonicalizes NaN payloads the pass-through
    dynamic path preserves."""
    e, m, s, i = fmt
    f = FPFormat(e, m, saturate=bool(s), ieee_inf=bool(i))
    static = np.asarray(jax.device_get(
        quantize(jnp.asarray(f16_space), f, impl="ref")))
    dyn = _dyn(f16_space, e, m, s, i, impl="ref")
    pallas = _dyn(f16_space, e, m, s, i, impl="interpret")
    assert_bits_equal(f"static-vs-dynamic-{_fmt_id(fmt)}",
                      f16_space, dyn, static, fmt=fmt,
                      nan_payload_free=True)
    assert_bits_equal(f"pallas-vs-dynamic-{_fmt_id(fmt)}",
                      f16_space, pallas, dyn, fmt=fmt)


def test_exhaustive_fp16_grid_idempotent(f16_space):
    """Quantizing to (5, 10) is the identity on the fp16 set (the values
    already lie on that grid) — the numpy f16 widening cross-check."""
    got = _dyn(f16_space, 5, 10, 0, 1)
    assert_bits_equal("fp16-idempotent", f16_space, got, f16_space,
                      fmt=(5, 10, 0, 1))


_ML_LEGS = []
try:
    import ml_dtypes

    _ML_LEGS = [
        ("fp16", (5, 10, 0, 1), np.float16),
        ("bf16", (8, 7, 0, 1), ml_dtypes.bfloat16),
        ("e5m2", (5, 2, 0, 1), ml_dtypes.float8_e5m2),
        ("e4m3fn", (4, 3, 0, 0), ml_dtypes.float8_e4m3fn),
    ]
except ImportError:
    pass


@pytest.mark.parametrize("leg", _ML_LEGS, ids=lambda l: l[0])
def test_exhaustive_fp16_vs_ml_dtypes(leg, f16_space):
    """For formats with a storage dtype, the oracle (hence the quantizer,
    by the tests above) matches the ml_dtypes RNE cast on every finite
    fp16 input. Non-finite inputs differ by documented convention: this
    repo's op-mode quantize passes ±inf/NaN through unchanged, while an
    fn-layout ml_dtypes cast maps inf to NaN."""
    name, (e, m, s, i), dt = leg
    x = f16_space
    fin = np.isfinite(x)
    want = oracle_quantize(x, e, m, bool(s), bool(i))
    cast = x.astype(dt).astype(np.float32)
    # NaN-payload-free: fn-layout overflow NaNs carry cast-specific payloads
    assert_bits_equal(f"mldtypes-{name}", x[fin], want[fin], cast[fin],
                      fmt=(e, m, s, i), nan_payload_free=True)
    # convention check on specials: quantize preserves them exactly
    assert np.array_equal(want[np.isinf(x)], x[np.isinf(x)])
    assert np.all(np.isnan(want[np.isnan(x)]))


# --------------------------------------------------------------------------
# randomized format/value corners (seeded — always runs in this tier)
# --------------------------------------------------------------------------

def _corner_values(rng, e, m, ieee_inf, n_random=512):
    """Adversarial inputs for one format: overflow boundary, subnormal
    range, grid half-way (tie) points, plus wide log-uniform noise."""
    _, min_exp, max_exp, max_finite = format_constants(e, m, bool(ieee_inf))
    with np.errstate(over="ignore", invalid="ignore"):
        mf32 = np.float32(max_finite)  # may be inf for e8 fn layouts
        specials = [0.0, -0.0, np.inf, -np.inf, np.nan, 1.0, -1.0,
                    max_finite,
                    float(np.nextafter(mf32, np.float32(np.inf))),
                    float(np.nextafter(mf32, np.float32(0))),
                    float(np.ldexp(1.0, min(max_exp + 1, 127))),
                    float(np.ldexp(1.0, min_exp)),
                    float(np.ldexp(1.0, min_exp - m)),      # smallest subn.
                    float(np.ldexp(1.0, min_exp - m - 1)),  # below the grid
                    float(np.ldexp(3.0, min_exp - m - 1))]  # subnormal tie
        # half-way (RNE tie) points n+0.5 grid units at random exponents
        # (e1-ieee formats have an empty normal range: no ties to draw)
        for _ in range(64 if max_exp >= min_exp else 0):
            E = int(rng.randint(min_exp, max_exp + 1))
            n = int(rng.randint(1 << m, 1 << (m + 1)))
            specials.append(float(np.ldexp(n + 0.5, E - m)))
        rand = (rng.randn(n_random)
                * np.power(10.0, rng.uniform(-42, 42, n_random)))
        vals = np.concatenate([np.asarray(specials, np.float64), rand])
        vals = vals.astype(np.float32)
    return np.concatenate([vals, -vals])


def _check_format(e, m, s, i, vals, tag):
    want = oracle_quantize(vals, e, m, bool(s), bool(i))
    got = _dyn(vals, e, m, s, i)
    assert_bits_equal(f"{tag}-dynamic", vals, got, want, fmt=(e, m, s, i))
    static = np.asarray(jax.device_get(quantize(
        jnp.asarray(vals), FPFormat(e, m, saturate=bool(s),
                                    ieee_inf=bool(i)), impl="ref")))
    # nan_payload_free: (8,7)/(5,10) draws hit the hardware astype path
    assert_bits_equal(f"{tag}-static", vals, static, want, fmt=(e, m, s, i),
                      nan_payload_free=True)


def test_randomized_format_corners():
    """60 random (e, m, saturate, ieee_inf) formats x ~1200 adversarial
    values each: dynamic and static paths vs the oracle, bit for bit."""
    rng = np.random.RandomState(20260728)
    for trial in range(60):
        e = int(rng.randint(1, 9))
        m = int(rng.randint(1, 24))
        s = int(rng.randint(2))
        i = int(rng.randint(2))
        vals = _corner_values(rng, e, m, i)
        _check_format(e, m, s, i, vals, f"corners-t{trial}-e{e}m{m}s{s}i{i}")


def test_randomized_pallas_parity():
    """The Pallas kernel (interpret mode) tracks the dynamic jnp path on
    randomized corner batches across random formats."""
    rng = np.random.RandomState(31337)
    for trial in range(12):
        e = int(rng.randint(1, 9))
        m = int(rng.randint(1, 24))
        s = int(rng.randint(2))
        i = int(rng.randint(2))
        vals = _corner_values(rng, e, m, i, n_random=256)
        ref = _dyn(vals, e, m, s, i, impl="ref")
        pal = _dyn(vals, e, m, s, i, impl="interpret")
        assert_bits_equal(f"pallas-t{trial}-e{e}m{m}s{s}i{i}",
                          vals, pal, ref, fmt=(e, m, s, i))


# --------------------------------------------------------------------------
# hypothesis property form (skips gracefully when hypothesis is absent —
# see the shim in tests/conftest.py)
# --------------------------------------------------------------------------

from hypothesis import given, settings  # noqa: E402
from hypothesis import strategies as st  # noqa: E402


@settings(max_examples=100, deadline=None)
@given(e=st.integers(1, 8), m=st.integers(1, 23),
       s=st.booleans(), i=st.booleans(),
       seed=st.integers(0, 2 ** 31 - 1))
def test_hypothesis_format_space(e, m, s, i, seed):
    """Property form of the corner contract: for ANY format in the search
    space and any adversarial value batch, dynamic == static == oracle."""
    rng = np.random.RandomState(seed % (2 ** 31))
    vals = _corner_values(rng, e, m, i, n_random=128)
    _check_format(e, m, int(s), int(i), vals,
                  f"hyp-e{e}m{m}s{int(s)}i{int(i)}-{seed}")
