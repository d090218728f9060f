"""Pure-jnp oracle for arbitrary (e, m) round-to-nearest-even quantization.

This is the TPU-native replacement for RAPTOR's MPFR emulation: instead of a
scalar correctly-rounded library call per operation, we round the *carrier*
(f32/f64) result of each op onto the representable grid of the target
``FPFormat`` with pure bit manipulation — fully vectorizable.

Semantics (validated against ml_dtypes for every hardware format in tests):
  * round-to-nearest, ties-to-even on the target grid
  * gradual underflow onto the target's subnormal grid
  * overflow -> +/-inf (IEEE layouts) / NaN (fn layouts) / +/-max_finite
    (``saturate`` formats)
  * NaN preserved, +/-inf preserved, +/-0 preserved
Known carrier-precision floor: inputs that are subnormal *in the carrier*
combined with a target whose exponent range exceeds the carrier's cannot be
re-normalized; irrelevant for every profiling configuration in this repo.
"""
from __future__ import annotations

import numpy as np
import jax.numpy as jnp
from jax import lax

_CARRIER = {
    jnp.dtype("float32"): (jnp.int32, 23),
    jnp.dtype("float64"): (jnp.int64, 52),
}


def _format_constants(exp_bits: int, man_bits: int, ieee_inf: bool):
    bias = (1 << (exp_bits - 1)) - 1
    max_exp = (1 << exp_bits) - (2 if ieee_inf else 1) - bias
    min_exp = 1 - bias
    if ieee_inf:
        max_finite = 2.0 ** max_exp * (2.0 - 2.0 ** (-min(man_bits, 52)))
    else:
        max_finite = 2.0 ** max_exp * (2.0 - 2.0 ** (1 - min(man_bits, 52)))
    min_normal = 2.0 ** min_exp
    sub_scale = 2.0 ** (min_exp - man_bits)
    return max_exp, max_finite, min_normal, sub_scale


def quantize_ref(x, exp_bits: int, man_bits: int, saturate: bool = False,
                 ieee_inf: bool = True):
    """Quantize ``x`` (f32 or f64) to the (exp_bits, man_bits) grid, RNE.

    Returns an array of the same dtype as ``x`` whose values all lie on the
    target format's representable grid.
    """
    dt = jnp.dtype(x.dtype)
    if dt not in _CARRIER:
        raise TypeError(f"carrier must be f32/f64, got {dt}")
    int_dtype, c_man = _CARRIER[dt]
    c_exp = 8 if c_man == 23 else 11
    np_int = np.int32 if c_man == 23 else np.int64

    _, max_finite, min_normal, sub_scale = _format_constants(
        exp_bits, man_bits, ieee_inf)
    k = c_man - man_bits  # mantissa bits to drop (<=0: nothing to drop)

    # ---- 1) normal-range mantissa RNE via the bit trick --------------------
    if k > 0:
        bits = lax.bitcast_convert_type(x, int_dtype)
        one = np_int(1)
        half = np_int(1 << (k - 1))
        lsb = lax.shift_right_logical(bits, np_int(k)) & one
        rounded = (bits + (half - one) + lsb) & np_int(~((1 << k) - 1))
        y = lax.bitcast_convert_type(rounded, dt)
    else:
        y = x

    # ---- 2) subnormal range: RNE onto the fixed-point grid -----------------
    # Only needed when the target exponent range is narrower than the
    # carrier's (otherwise the carrier-aligned bit trick already lands on the
    # right subnormal grid — see tests vs ml_dtypes bf16).
    finfo = np.finfo(dt)
    if exp_bits < c_exp and sub_scale >= float(finfo.tiny):
        ss = np.array(sub_scale, dt)
        mn = np.array(min_normal, dt)
        x_sub = jnp.rint(x / ss) * ss
        y = jnp.where(jnp.abs(x) < mn, x_sub, y)

    # ---- 3) overflow --------------------------------------------------------
    if max_finite <= float(finfo.max):
        mf = np.array(max_finite, dt)
        ovf = jnp.abs(y) > mf
        if saturate:
            y = jnp.where(ovf, jnp.sign(y) * mf, y)
        elif ieee_inf:
            y = jnp.where(ovf, jnp.sign(y) * np.array(np.inf, dt), y)
        else:  # fn layout, non-saturating: overflow is NaN (ml_dtypes cast)
            y = jnp.where(ovf, np.array(np.nan, dt), y)

    # ---- 4) specials ----------------------------------------------------------
    y = jnp.where(jnp.isnan(x), x, y)
    y = jnp.where(jnp.isinf(x), x, y)  # inf passes through even when saturating
    return y


def quantize_ref_fmt(x, fmt):
    """Convenience wrapper taking an ``FPFormat``."""
    return quantize_ref(x, fmt.exp_bits, fmt.man_bits, fmt.saturate, fmt.ieee_inf)


# ---------------------------------------------------------------------------
# runtime-parameterized variant: (e, m, saturate, ieee_inf) as traced values
# ---------------------------------------------------------------------------
#
# ``quantize_ref`` above specializes the computation on the format at trace
# time (python branches, numpy constants), so every distinct format costs a
# retrace + recompile. ``dynamic_row_params`` + ``apply_row_params`` take
# the format fields as *traced values*: one compiled executable serves every
# (e, m, saturate, ieee_inf), which is what collapses a precision-policy
# sweep to a single XLA compilation. All static branches become lane-wise
# ``where`` gates; the static identity fast path becomes the in-kernel
# ``man_bits >= carrier`` gate. Kept free of python-level f64 branches when
# the carrier is f32 so the Pallas kernels can call them directly.


def _pow2(n, dt):
    """Exact 2**n in carrier dtype ``dt`` for traced int32 ``n``, built by
    writing the exponent field directly (bitcast) — no transcendentals, so it
    lowers inside a Pallas kernel. Saturates to 0 below the normal range and
    to +inf above it; both ends are gated off by the callers."""
    if jnp.dtype(dt) == jnp.dtype(jnp.float32):
        int_dtype, man, bias, emax = jnp.int32, 23, 127, 255
    else:
        int_dtype, man, bias, emax = jnp.int64, 52, 1023, 2047
    biased = jnp.clip(n + bias, 0, emax).astype(int_dtype)
    return lax.bitcast_convert_type(
        jnp.left_shift(biased, jnp.asarray(man, int_dtype)), jnp.dtype(dt))


def dynamic_row_params(exp_bits, man_bits, saturate, ieee_inf, fault=0,
                       dtype=jnp.float32):
    """Derived rounding constants for the runtime quantizer, elementwise.

    Every quantity the runtime quantizer derives from the format fields —
    rounding masks, range bounds, gates, the fault XOR mask — but none of
    the array-side math. Inputs may be python ints, traced scalars, or
    whole ``(num_sites,)`` table columns: the math is elementwise, so one
    call derives the constants for an entire format table at once. That is
    the point of the split — a runtime-table sweep derives its ~30 scalar
    ops once per *table* instead of once per *site*, which is what keeps
    the swept executable's graph (and XLA compile time) near the static
    transform's. Returns a dict of arrays parallel to the inputs.
    """
    dt = jnp.dtype(dtype)
    if dt not in _CARRIER:
        raise TypeError(f"carrier must be f32/f64, got {dt}")
    int_dtype, c_man = _CARRIER[dt]
    c_exp = 8 if c_man == 23 else 11
    finfo = np.finfo(dt)

    e = jnp.asarray(exp_bits, jnp.int32)
    m = jnp.asarray(man_bits, jnp.int32)
    sat = jnp.asarray(saturate, jnp.bool_)
    inf = jnp.asarray(ieee_inf, jnp.bool_)
    fault = jnp.asarray(fault, jnp.int32)

    bias = jnp.left_shift(1, e - 1) - 1
    max_exp = jnp.left_shift(1, e) - jnp.where(inf, 2, 1) - bias
    min_exp = 1 - bias
    m_eff = jnp.minimum(m, c_man)
    two = np.array(2.0, dt)
    max_finite = _pow2(max_exp, dt) * (
        two - _pow2(jnp.where(inf, -m_eff, 1 - m_eff), dt))
    min_normal = _pow2(min_exp, dt)
    sub_scale = _pow2(min_exp - m, dt)

    one = jnp.asarray(1, int_dtype)
    k = jnp.clip(c_man - m, 0, c_man)
    kk = k.astype(int_dtype)
    half = jnp.left_shift(one, jnp.maximum(kk - one, 0))
    keep = jnp.bitwise_not(jnp.left_shift(one, kk) - one)
    tiny = np.array(finfo.tiny, dt)
    use_sub = (e < c_exp) & (sub_scale >= tiny)
    ss = jnp.where(use_sub, sub_scale, np.array(1.0, dt))
    # exact reciprocal: ss is a power of two >= the carrier's tiny, so 1/ss
    # = 2**(m - min_exp) is finite and x * (1/ss) == x / ss bit-for-bit.
    # Built as a power of two rather than divided: a TPU vector divide is
    # not guaranteed correctly rounded, and the multiply is much cheaper
    # for XLA's CPU backend to compile than the division.
    ssinv = jnp.where(use_sub, _pow2(m - min_exp, dt), np.array(1.0, dt))
    ovf_gate = max_finite <= np.array(finfo.max, dt)
    # overflow magnitude for the sign-carrying cases; the fn (overflow->NaN)
    # case is selected separately in apply so the stored NaN stays the
    # positive quiet-NaN constant, never a sign-flipped product
    ovf_mag = jnp.where(sat, max_finite, np.array(np.inf, dt))
    ovf_nan = ~sat & ~inf
    identity = (m >= c_man) & (e >= c_exp) & inf & ~sat
    fshift = jnp.maximum(fault - 1, 0).astype(int_dtype)
    fmask = jnp.where(fault > 0, jnp.left_shift(one, fshift),
                      jnp.asarray(0, int_dtype))
    return dict(kk=kk, half=half, keep=keep, knz=k > 0,
                use_sub=use_sub, ss=ss, ssinv=ssinv, min_normal=min_normal,
                ovf_gate=ovf_gate, max_finite=max_finite, ovf_mag=ovf_mag,
                ovf_nan=ovf_nan, identity=identity, fmask=fmask)


def apply_row_params(x, p):
    """Quantize carrier array ``x`` with precomputed row constants ``p``
    (one row of :func:`dynamic_row_params`, i.e. scalar entries), including
    the fault-channel XOR (``fmask == 0`` is an exact bit no-op)."""
    dt = jnp.dtype(x.dtype)
    int_dtype, _ = _CARRIER[dt]
    one = jnp.asarray(1, int_dtype)

    # ---- 1) normal-range mantissa RNE, traced shift amounts ----------------
    # bit k of a two's-complement int is shift-direction agnostic, so the
    # arithmetic right_shift (which broadcasts) stands in for the logical one
    bits = lax.bitcast_convert_type(x, int_dtype)
    lsb = jnp.bitwise_and(jnp.right_shift(bits, p["kk"]), one)
    rounded = jnp.bitwise_and(bits + (p["half"] - one) + lsb, p["keep"])
    y = jnp.where(p["knz"], lax.bitcast_convert_type(rounded, dt), x)

    # ---- 2) subnormal range: RNE onto the fixed-point grid -----------------
    x_sub = jnp.rint(x * p["ssinv"]) * p["ss"]
    y = jnp.where(p["use_sub"] & (jnp.abs(x) < p["min_normal"]), x_sub, y)

    # ---- 3) overflow --------------------------------------------------------
    ovf = p["ovf_gate"] & (jnp.abs(y) > p["max_finite"])
    ovf_val = jnp.where(p["ovf_nan"], np.array(np.nan, dt),
                        jnp.copysign(p["ovf_mag"], y))
    y = jnp.where(ovf, ovf_val, y)

    # ---- 4) specials + identity gate (all branches restore x) --------------
    y = jnp.where(jnp.isnan(x) | jnp.isinf(x) | p["identity"], x, y)

    # ---- 5) fault channel ---------------------------------------------------
    yb = lax.bitcast_convert_type(y, int_dtype)
    return lax.bitcast_convert_type(jnp.bitwise_xor(yb, p["fmask"]), dt)


# ---------------------------------------------------------------------------
# fused-kernel epilogue: the full runtime row, applied to a store value
# ---------------------------------------------------------------------------
#
# The producing kernels (flash_attention, rwkv6) fuse the dynamic quantize as
# an epilogue on their output stores, driven by the same (4,) int32 SMEM
# scalar-prefetch row the standalone quantize kernel uses. The epilogue must
# be bit-identical to ``ops.quantize_dynamic`` applied to the *stored* value
# (quantize the value after it has been cast to the output dtype, on an f32
# carrier), including the fault channel packed into the row's fourth field —
# so it lives here, next to the quantizer, with no repro.core dependencies
# (kernels must stay importable before repro.core finishes initializing).


def quantize_epilogue(y, fmt_ref):
    """Apply a runtime format row to 2-D kernel output ``y`` just before the
    store: decode ``field3 = ieee_inf | (bit_index + 1) << 1``, quantize the
    f32 carrier, XOR the armed fault bit, cast back to ``y.dtype``.

    ``fmt_ref`` is indexable as a (4,) int32 vector — an SMEM scalar-prefetch
    ref inside a Pallas kernel, or a plain array under ``interpret=True``.
    Bit-identical to ``ops.quantize_dynamic(y, row, impl='ref')`` for any
    f32-carrier ``y``; the identity row (and any clean row with fault 0)
    passes values through unchanged, so the fused kernels can always run
    with the epilogue wired in. The standalone runtime quantize kernel is
    this epilogue on a lane-dense block."""
    # Mosaic lowers bitcasts (``_pow2``) and float math on vectors only, so
    # the row constants are derived on one (1, lanes) broadcast row rather
    # than on SMEM scalars; apply_row_params broadcasts it down the sublanes
    e, m, s, f3 = (jnp.full((1, y.shape[-1]), fmt_ref[i], jnp.int32)
                   for i in range(4))
    fault = jnp.right_shift(f3, 1)
    inf = jnp.bitwise_and(f3, 1)
    p = dynamic_row_params(e, m, s, inf, fault)
    return apply_row_params(y.astype(jnp.float32), p).astype(y.dtype)
