"""Pallas TPU kernel for arbitrary (e, m) RNE quantization.

This is the hot path of the profiling runtime: in op-mode every FP primitive
result in a truncated scope passes through this quantizer, so it must run at
VPU rate. The kernel is pure elementwise integer bit manipulation on
``(8,128)``-aligned VMEM tiles — no MXU, no transcendentals, one pass.

Target layout: input flattened to (rows, 1024) f32, grid over row-blocks,
each block (block_rows, 1024) resident in VMEM. What bounds the block is
the compiler's default *scoped* VMEM limit (16 MiB on v5e), not the
physical VMEM: in and out are double-buffered and the elementwise math
keeps block-sized temporaries live. At block_rows=1024 (4 MiB per block)
the v5e compile asks for a 32 MiB scoped allocation and is refused; 512
rows (2 MiB per block) compiles for both kernels (tests/test_tpu_compile.py).
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.quantize_em import ref as _ref

LANES = 1024  # 8 * 128 lane multiple
BLOCK_ROWS = 512  # largest power of two that fits v5e's scoped VMEM


def _quantize_block(x, *, exp_bits: int, man_bits: int, saturate: bool,
                    ieee_inf: bool):
    """Elementwise (e,m) RNE quantization of an f32 block (traced inside the
    kernel; mirrors ref.quantize_ref, kept separate so the kernel never
    touches code with f64 branches)."""
    bias = (1 << (exp_bits - 1)) - 1
    max_exp = (1 << exp_bits) - (2 if ieee_inf else 1) - bias
    min_exp = 1 - bias
    if ieee_inf:
        max_finite = 2.0 ** max_exp * (2.0 - 2.0 ** (-man_bits))
    else:
        max_finite = 2.0 ** max_exp * (2.0 - 2.0 ** (1 - man_bits))
    min_normal = 2.0 ** min_exp
    sub_scale = 2.0 ** (min_exp - man_bits)
    k = 23 - man_bits

    y = x
    if k > 0:
        bits = lax.bitcast_convert_type(x, jnp.int32)
        one = np.int32(1)
        half = np.int32(1 << (k - 1))
        lsb = lax.shift_right_logical(bits, np.int32(k)) & one
        rounded = (bits + (half - one) + lsb) & np.int32(~((1 << k) - 1))
        y = lax.bitcast_convert_type(rounded, jnp.float32)

    f32 = np.finfo(np.float32)
    if exp_bits < 8 and sub_scale >= float(f32.tiny):
        ss = np.float32(sub_scale)
        mn = np.float32(min_normal)
        x_sub = jnp.rint(x / ss) * ss
        y = jnp.where(jnp.abs(x) < mn, x_sub, y)

    if max_finite <= float(f32.max):
        mf = np.float32(max_finite)
        ovf = jnp.abs(y) > mf
        if saturate:
            y = jnp.where(ovf, jnp.sign(y) * mf, y)
        elif ieee_inf:
            y = jnp.where(ovf, jnp.sign(y) * np.float32(np.inf), y)
        else:
            y = jnp.where(ovf, np.float32(np.nan), y)

    y = jnp.where(jnp.isnan(x), x, y)
    y = jnp.where(jnp.isinf(x), x, y)
    return y


def _kernel(x_ref, o_ref, *, exp_bits, man_bits, saturate, ieee_inf):
    o_ref[...] = _quantize_block(
        x_ref[...], exp_bits=exp_bits, man_bits=man_bits, saturate=saturate,
        ieee_inf=ieee_inf,
    )


@functools.partial(
    jax.jit,
    static_argnames=("exp_bits", "man_bits", "saturate", "ieee_inf",
                     "block_rows", "interpret"),
)
def quantize_2d(x, *, exp_bits: int, man_bits: int, saturate: bool = False,
                ieee_inf: bool = True, block_rows: int = BLOCK_ROWS,
                interpret: bool = False):
    """Quantize a (rows, LANES) f32 array on the (e,m) grid via pallas_call."""
    assert x.ndim == 2 and x.shape[1] == LANES, x.shape
    rows = x.shape[0]
    br = min(block_rows, rows)
    grid = (pl.cdiv(rows, br),)
    return pl.pallas_call(
        functools.partial(_kernel, exp_bits=exp_bits, man_bits=man_bits,
                          saturate=saturate, ieee_inf=ieee_inf),
        grid=grid,
        in_specs=[pl.BlockSpec((br, LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((br, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        name="quantize_em",
        interpret=interpret,
    )(x)


# ---------------------------------------------------------------------------
# runtime-parameterized kernel: format read from SMEM, not baked into the code
# ---------------------------------------------------------------------------

def _dyn_kernel(fmt_ref, x_ref, o_ref):
    """``fmt_ref`` is the scalar-prefetched (4,) int32 format row
    (exp_bits, man_bits, saturate, ieee_inf | fault << 1) living in SMEM;
    the block math is the fused kernels' epilogue (pure bit ops + where
    gates, f32 only), so one compiled kernel serves every format."""
    o_ref[...] = _ref.quantize_epilogue(x_ref[...], fmt_ref)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def quantize_2d_dynamic(x, fmt, *, block_rows: int = BLOCK_ROWS,
                        interpret: bool = False):
    """Quantize a (rows, LANES) f32 array onto the grid described by the
    runtime (4,) int32 row ``fmt`` (fault channel included) — same
    layout/grid as ``quantize_2d`` but compiled once for all formats."""
    assert x.ndim == 2 and x.shape[1] == LANES, x.shape
    rows = x.shape[0]
    br = min(block_rows, rows)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(pl.cdiv(rows, br),),
        in_specs=[pl.BlockSpec((br, LANES), lambda i, fmt_ref: (i, 0))],
        out_specs=pl.BlockSpec((br, LANES), lambda i, fmt_ref: (i, 0)),
    )
    return pl.pallas_call(
        _dyn_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        name="quantize_em_dynamic",
        interpret=interpret,
    )(jnp.asarray(fmt, jnp.int32), x)
