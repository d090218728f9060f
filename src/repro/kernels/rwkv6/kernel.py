"""Pallas TPU kernel for the RWKV-6 (Finch) WKV recurrence, chunked.

Per head h with per-token, per-channel decay w_t in (0,1):
    S_t = diag(w_t) S_{t-1} + k_t^T v_t
    y_t = r_t (S_{t-1} + diag(u) k_t^T v_t)

Grid = (batch*heads, chunks); the chunk axis runs in order, streaming one
chunk of length C per program through VMEM while the (hd x hd) state stays
in a VMEM scratch across the chunk axis (so VMEM use does not grow with the
sequence). Within a chunk the recurrence is the exact step form (fori_loop
over C tokens — numerically identical to the reference; the decay products
stay implicit so no 1/A overflow issues). This is the TPU analogue
of the within-chunk/cross-chunk split used by GPU linear-attention kernels,
re-blocked for VMEM instead of shared memory.

VMEM per program at (C=64, hd=64): r/k/v/w 4x64x64x4 = 64 KiB + state
16 KiB + y 16 KiB — tiny; the win is HBM locality of the streamed chunks.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.quantize_em import ref as _qref


def _wkv_kernel(*refs, chunk: int, quantized: bool = False):
    if quantized:
        # fused epilogue: (4,) int32 runtime format row via SMEM scalar
        # prefetch, applied to the per-chunk y stores (the recurrence state
        # sT is a carry, not a truncation site — it stays exact)
        (fmt_ref, r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref,
         y_ref, sT_ref, s_ref) = refs
    else:
        r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, y_ref, sT_ref, s_ref = refs
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _():
        s_ref[...] = s0_ref[0].astype(jnp.float32)

    u = u_ref[0].astype(jnp.float32)                       # (1, hd)

    def tok(t, s):
        # per-token rows are loaded from and stored to the refs: Mosaic has
        # no dynamic_slice / dynamic_update_slice on values
        row = (0, 0, pl.ds(t, 1), slice(None))
        kt = k_ref[row].astype(jnp.float32)                # (1, hd)
        vt = v_ref[row].astype(jnp.float32)
        rt = r_ref[row].astype(jnp.float32)
        wt = w_ref[row].astype(jnp.float32)
        kv = kt.T @ vt                                     # (hd, hd)
        ukv = (u * kt).T @ vt                              # diag(u) kv
        y_ref[row] = (rt @ (s + ukv)).astype(y_ref.dtype)
        return wt.T * s + kv

    s_ref[...] = jax.lax.fori_loop(0, chunk, tok, s_ref[...])
    if quantized:
        y_ref[0, 0] = _qref.quantize_epilogue(y_ref[0, 0], fmt_ref)

    @pl.when(c == pl.num_programs(1) - 1)
    def _():
        sT_ref[0] = s_ref[...].astype(sT_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv6_pallas(r, k, v, w, u, s0, *, chunk: int = 64,
                interpret: bool = False, out_fmt=None):
    """r/k/v/w: (B, H, S, hd); u: (H, hd); s0: (B, H, hd, hd) f32.
    Returns (y (B, H, S, hd) f32, sT (B, H, hd, hd) f32).

    ``out_fmt`` (optional): a (4,) int32 runtime format row; when given, the
    dynamic quantize is fused into the per-chunk ``y`` stores (scalar
    prefetch — format swaps are data, zero recompiles). ``sT`` is returned
    unquantized either way."""
    B, H, S, hd = r.shape
    chunk = min(chunk, S)
    assert S % chunk == 0, (S, chunk)
    nch = S // chunk

    def shape4(t):
        return t.reshape(B * H, nch, chunk, hd)

    # (B*H, 1, hd): a block's last two dims must tile (8, 128) or span the
    # array, so the per-head bonus row keeps a unit sublane axis
    u_r = jnp.broadcast_to(u[None], (B, H, hd)).reshape(B * H, 1, hd)
    s0_r = s0.reshape(B * H, hd, hd)

    def spec(block, ix):
        # index maps take the trailing prefetch ref when there is one
        return pl.BlockSpec(block, lambda i, c, *_: ix(i, c))

    seq = ((1, 1, chunk, hd), lambda i, c: (i, c, 0, 0))
    head = ((1, hd, hd), lambda i, c: (i, 0, 0))
    prefetch = () if out_fmt is None else (jnp.asarray(out_fmt, jnp.int32),)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(B * H, nch),
        in_specs=[spec(*seq)] * 4 + [
            spec((1, 1, hd), lambda i, c: (i, 0, 0)), spec(*head)],
        out_specs=[spec(*seq), spec(*head)],
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32)],
    )
    y, sT = pl.pallas_call(
        functools.partial(_wkv_kernel, chunk=chunk,
                          quantized=out_fmt is not None),
        grid_spec=grid_spec,
        name="wkv6",
        out_shape=[
            jax.ShapeDtypeStruct((B * H, nch, chunk, hd), jnp.float32),
            jax.ShapeDtypeStruct((B * H, hd, hd), jnp.float32),
        ],
        interpret=interpret,
    )(*prefetch, shape4(r), shape4(k), shape4(v), shape4(w), u_r, s0_r)
    return y.reshape(B, H, S, hd), sT.reshape(B, H, hd, hd)
