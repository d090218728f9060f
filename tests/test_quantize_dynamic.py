"""Runtime-parameterized quantization must be bit-for-bit identical to the
static-format path: the whole point of `quantize_dynamic` is that swapping a
format table cell is indistinguishable from retracing with a new constant
format — across the full DEFAULT_WIDTHS ladder, both impls, the
saturate/ieee_inf overflow corners, float8 storage dtypes, and the f64
carrier."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.formats import FPFormat, parse_format
from repro.kernels.quantize_em.ops import (
    quantize, quantize_dynamic, format_row, IDENTITY_ROW,
)
from repro.search.driver import DEFAULT_WIDTHS


def _vec(n=4096, seed=0):
    rng = np.random.RandomState(seed)
    x = np.concatenate([
        rng.randn(n).astype(np.float32)
        * 10 ** rng.uniform(-12, 12, n).astype(np.float32),
        np.array([0.0, -0.0, 1.0, -1.0, np.inf, -np.inf, np.nan,
                  65504.0, 65505.0, 448.0, 464.0, 480.0, 3e-5,
                  5.96e-8, 2.98e-8, 1e-45, -1e-45, 2 ** -126, 2 ** -133],
                 np.float32)])
    return jnp.asarray(x)


def _assert_same_bits(a, b, fmt):
    an = np.asarray(jax.device_get(a))
    bn = np.asarray(jax.device_get(b))
    assert an.dtype == bn.dtype
    av = an.view(np.uint8 if an.dtype.itemsize == 1 else
                 np.int64 if an.dtype.itemsize == 8 else np.int32)
    bv = bn.view(av.dtype)
    bad = np.where(av != bv)[0]
    assert len(bad) == 0, (fmt, [(an[i], bn[i]) for i in bad[:5]])


@pytest.mark.parametrize("impl", ["ref", "interpret"])
@pytest.mark.parametrize("m", DEFAULT_WIDTHS)
def test_ladder_bit_for_bit(m, impl):
    """Every rung of the search ladder, static vs runtime-table formats.
    m=23 exercises the in-kernel identity gate against the static identity
    fast path; m=7/10 exercise it against the hardware convert pair."""
    x = _vec()
    fmt = FPFormat(8, m)
    _assert_same_bits(quantize(x, fmt, impl=impl),
                      quantize_dynamic(x, format_row(fmt), impl=impl), fmt)


@pytest.mark.parametrize("impl", ["ref", "interpret"])
@pytest.mark.parametrize("spec", ["e4m3", "e4m3fn", "e5m2", "fp16", "bf16",
                                  "e6m9s", "e2m1", "e5m14", "e4m0"])
def test_overflow_corners_bit_for_bit(spec, impl):
    """saturate (e4m3/e6m9s), fn-layout NaN overflow (e4m3fn), IEEE inf
    (e5m2), and the hardware formats — same bits through both entry points."""
    x = _vec(seed=7)
    fmt = parse_format(spec)
    _assert_same_bits(quantize(x, fmt, impl=impl),
                      quantize_dynamic(x, format_row(fmt), impl=impl), fmt)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float16,
                                   jnp.float8_e4m3fn, jnp.float8_e5m2])
def test_narrow_storage_dtypes(dtype):
    """Sub-f32 storage (incl. float8): dtype round-trips and the values
    agree bitwise with the static path."""
    x = jnp.asarray(np.random.RandomState(3).randn(512), jnp.float32)
    xs = x.astype(dtype)
    for spec in ("e4m2", "e3m1", "e4m3", "fp32"):
        fmt = parse_format(spec)
        a = quantize(xs, fmt, impl="ref")
        b = quantize_dynamic(xs, format_row(fmt), impl="ref")
        assert b.dtype == xs.dtype
        _assert_same_bits(a, b, (dtype, fmt))


def test_f64_carrier_bit_for_bit():
    with jax.enable_x64(True):
        x64 = jnp.asarray(
            np.random.RandomState(0).randn(256).astype(np.float64) / 3.0)
        for fmt in (parse_format("5_14"), FPFormat(8, 30), FPFormat(11, 52),
                    parse_format("e4m3")):
            _assert_same_bits(quantize(x64, fmt, impl="ref"),
                              quantize_dynamic(x64, format_row(fmt),
                                               impl="ref"), fmt)


def test_identity_row_is_bitwise_identity():
    x = _vec()
    y = quantize_dynamic(x, IDENTITY_ROW, impl="ref")
    _assert_same_bits(x, y, "identity")


@pytest.mark.parametrize("impl", ["ref", "interpret"])
def test_vmap_over_format_table(impl):
    """A (K, 4) table vmapped over its leading axis equals K separate static
    quantizations — the batched-policy-sweep building block."""
    x = _vec(n=512, seed=1)
    fmts = [FPFormat(8, m) for m in (15, 7, 3)] + [parse_format("e4m3")]
    table = jnp.asarray(np.stack([format_row(f) for f in fmts]))
    rows = jax.vmap(lambda r: quantize_dynamic(x, r, impl=impl))(table)
    for i, fmt in enumerate(fmts):
        _assert_same_bits(rows[i], quantize(x, fmt, impl=impl), fmt)


def test_traced_format_single_compile():
    """The format is runtime data: one jitted callable serves every format
    without retracing (the executable-level zero-recompile guarantee)."""
    x = _vec(n=256, seed=2)
    traces = []

    @jax.jit
    def q(row):
        traces.append(1)
        return quantize_dynamic(x, row, impl="ref")

    for fmt in (FPFormat(8, 7), FPFormat(5, 2), parse_format("e4m3")):
        _assert_same_bits(q(jnp.asarray(format_row(fmt))),
                          quantize(x, fmt, impl="ref"), fmt)
    assert len(traces) == 1
