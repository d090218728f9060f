"""Public jit'd quantization op with implementation dispatch.

``impl``:
  * ``'auto'``      — pallas on TPU, pure-jnp ref elsewhere (CPU dry-runs must
                      not lower pallas kernels)
  * ``'ref'``       — pure-jnp oracle
  * ``'pallas'``    — compiled pallas kernel (TPU)
  * ``'interpret'`` — pallas kernel in interpret mode (CPU validation)

Fast paths (RAPTOR's zero-overhead hardware mode): when (e,m) matches a
hardware storage type and overflow semantics agree, emit a plain convert
pair instead of the bit-math.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.formats import FPFormat, parse_format
from repro.kernels.quantize_em import kernel as _kernel
from repro.kernels.quantize_em import ref as _ref

_HW_DTYPES = {(8, 7): jnp.bfloat16, (5, 10): jnp.float16}

# Runtime format vectors: (exp_bits, man_bits, saturate, ieee_inf) as int32.
# IDENTITY_ROW is at least as fine as any carrier grid and IEEE, so the
# dynamic quantizer's in-kernel identity gate passes values through
# unchanged — the runtime analogue of the static identity fast path.
IDENTITY_ROW = np.array([11, 52, 0, 1], np.int32)


def format_row(fmt) -> np.ndarray:
    """Lower an ``FPFormat`` (or spec string) to its (4,) int32 runtime row."""
    fmt = parse_format(fmt)
    return np.array([fmt.exp_bits, fmt.man_bits, int(fmt.saturate),
                     int(fmt.ieee_inf)], np.int32)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def quantize(x, fmt, *, impl: str = "auto"):
    """Round every element of float array ``x`` onto the (e,m) grid of ``fmt``.

    Non-float inputs pass through unchanged. The result dtype equals the
    input dtype (values merely lie on the coarser grid) — op-mode semantics.
    """
    fmt: FPFormat = parse_format(fmt)
    dt = jnp.dtype(x.dtype) if hasattr(x, "dtype") else None
    if dt is None or not jnp.issubdtype(dt, jnp.floating):
        return x

    # identity: target grid at least as fine as the storage grid. Derived
    # from finfo so any float dtype works (float8_*, future formats) instead
    # of KeyError-ing outside a hardcoded table.
    finfo = jnp.finfo(dt)
    storage_bits = finfo.nmant
    storage_exp = finfo.bits - 1 - finfo.nmant
    if (fmt.man_bits >= storage_bits and fmt.exp_bits >= storage_exp
            and not fmt.saturate and fmt.ieee_inf):
        return x

    # hardware convert-pair fast path
    hw = _HW_DTYPES.get((fmt.exp_bits, fmt.man_bits))
    if hw is not None and not fmt.saturate and fmt.ieee_inf:
        return x.astype(hw).astype(dt)

    # carrier selection: f64 stays f64 (CPU), everything else goes via f32
    if dt == jnp.dtype(jnp.float64):
        return _ref.quantize_ref_fmt(x, fmt)

    xf = x.astype(jnp.float32)
    if impl == "auto":
        impl = "pallas" if _on_tpu() else "ref"

    if impl == "ref":
        y = _ref.quantize_ref_fmt(xf, fmt)
    elif impl in ("pallas", "interpret"):
        y = _pallas_any_shape(xf, fmt, interpret=(impl == "interpret"))
    else:
        raise ValueError(f"unknown impl {impl!r}")
    return y.astype(dt)


def quantize_dynamic(x, fmt, *, impl: str = "auto"):
    """Runtime-parameterized ``quantize``: ``fmt`` is a (4,) int32 array
    (exp_bits, man_bits, saturate, ieee_inf) whose values are *runtime* data
    — python ints, concrete arrays, or tracers (e.g. a row of a vmapped
    format table).

    One compiled executable serves every format: the static identity and
    hardware-convert fast paths are replaced by the quantizer's in-kernel
    ``man_bits >= carrier`` identity gate, so sweeping formats never
    retraces or recompiles. Bit-for-bit identical to the static entry point
    for every format with ``man_bits <= 23`` on f32 carriers (``<= 52`` on
    f64) — see tests/test_quantize_dynamic.py. Non-float inputs pass
    through; the result dtype equals the input dtype.

    **Fault channel** (``repro.guardrails.faults``): the high bits of the
    row's fourth field carry an optional bit-flip fault,
    ``field3 = ieee_inf | (bit_index + 1) << 1``. Every impl decodes it
    (the Pallas kernel in-kernel) and XORs the chosen carrier bit into every
    (already quantized) element. A clean row (field3 in {0, 1}) decodes to
    fault 0 and the XOR is an exact no-op, so arming or disarming a fault is
    a table *value* change on the same compiled executable: zero
    recompiles."""
    dt = jnp.dtype(x.dtype) if hasattr(x, "dtype") else None
    if dt is None or not jnp.issubdtype(dt, jnp.floating):
        return x
    fmt = jnp.asarray(fmt, jnp.int32)
    # scalar unpack, no scatter: the old `fmt.at[3].set(fmt[3] & 1)` strip
    # emitted one (batched, under vmap) scatter per truncation site, which
    # dominated trace+compile time on table sweeps (hundreds of sites per
    # program — the batched sweep's first call regressed below the static
    # path on exactly this).
    e, m, s, f3 = fmt[0], fmt[1], fmt[2], fmt[3]
    fault = jnp.right_shift(f3, 1)
    inf = jnp.bitwise_and(f3, 1)

    # carrier selection mirrors the static path: f64 stays f64, rest via f32
    if dt == jnp.dtype(jnp.float64):
        p = _ref.dynamic_row_params(e, m, s, inf, fault, jnp.float64)
        return _ref.apply_row_params(x, p)

    xf = x.astype(jnp.float32)
    if impl == "auto":
        impl = "pallas" if _on_tpu() else "ref"

    if impl == "ref":
        p = _ref.dynamic_row_params(e, m, s, inf, fault)
        return _ref.apply_row_params(xf, p).astype(dt)
    if impl in ("pallas", "interpret"):
        # the kernel decodes the fault channel itself (quantize_epilogue)
        y = _pallas_any_shape_dynamic(xf, fmt, interpret=(impl == "interpret"))
        return y.astype(dt)
    raise ValueError(f"unknown impl {impl!r}")


# --------------------------------------------------------------------------
# prepared-table path: derive row constants once, apply cheaply per site
# --------------------------------------------------------------------------
#
# A runtime-table sweep quantizes hundreds of sites per program, and the
# dynamic quantizer spends about as many graph ops deriving constants from
# the format fields (bias, bounds, masks — ~30 scalar ops) as it does on
# the array math. Inlining that derivation at every site made the swept
# executable's graph several times the static transform's and pushed its
# one-off XLA compile above SIX static compiles (the "first call slower
# than static" regression). ``prepare_dynamic`` derives the constants for
# the WHOLE table in one vectorized block; each site then slices its row
# and runs only the array-side math, jit-wrapped so tracing is paid once
# per distinct operand shape instead of once per site.

@jax.jit
def _apply_row(x, prep, site):
    """jit-shared slice + apply: the row index is a *traced* scalar, so one
    trace (and one compiled subgraph) serves every site with ``x``'s shape —
    per-site trace cost collapses from the whole quantizer to one call."""
    return _ref.apply_row_params(x, {k: v[site] for k, v in prep.items()})


def prepare_dynamic(table, dtype=jnp.float32):
    """Vectorized derived constants for every row of a ``(num_sites, 4)``
    format table (fault channel included): one dict of ``(num_sites,)``
    arrays consumed by :func:`quantize_prepared`."""
    t = jnp.asarray(table, jnp.int32)
    e, m, s, f3 = t[..., 0], t[..., 1], t[..., 2], t[..., 3]
    return _ref.dynamic_row_params(e, m, s, jnp.bitwise_and(f3, 1),
                                   jnp.right_shift(f3, 1), dtype)


def quantize_prepared(x, prep, site: int):
    """Quantize ``x`` onto row ``site`` of a prepared table — bit-identical
    to ``quantize_dynamic(x, table[site], impl='ref')``. ``prep`` must have
    been built for ``x``'s carrier (f32 for everything but f64 inputs)."""
    dt = jnp.dtype(x.dtype) if hasattr(x, "dtype") else None
    if dt is None or not jnp.issubdtype(dt, jnp.floating):
        return x
    if dt == jnp.dtype(jnp.float64):
        return _apply_row(x, prep, site)
    return _apply_row(x.astype(jnp.float32), prep, site).astype(dt)


def _to_rows(xf):
    """Flatten/pad an f32 array to (rows, LANES); no copy when lane-aligned."""
    lanes = _kernel.LANES
    n = xf.size
    rows = -(-n // lanes)
    pad = rows * lanes - n
    flat = jnp.ravel(xf)
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat.reshape(rows, lanes), n, pad


def _from_rows(y2d, shape, n, pad):
    out = jnp.ravel(y2d)
    if pad:
        out = out[:n]
    return out.reshape(shape)


def _pallas_any_shape(xf, fmt: FPFormat, *, interpret: bool):
    """Flatten/pad to (rows, LANES), run the kernel, restore the shape."""
    if xf.size == 0:
        return xf
    x2d, n, pad = _to_rows(xf)
    y2d = _kernel.quantize_2d(
        x2d,
        exp_bits=fmt.exp_bits, man_bits=fmt.man_bits, saturate=fmt.saturate,
        ieee_inf=fmt.ieee_inf, interpret=interpret,
    )
    return _from_rows(y2d, xf.shape, n, pad)


def _pallas_any_shape_dynamic(xf, fmt, *, interpret: bool):
    if xf.size == 0:
        return xf
    x2d, n, pad = _to_rows(xf)
    y2d = _kernel.quantize_2d_dynamic(x2d, fmt, interpret=interpret)
    return _from_rows(y2d, xf.shape, n, pad)
