"""Pallas TPU flash attention (causal / sliding-window / GQA).

Tiling: grid = (batch*kv_heads, q_head_group, q_blocks); each program owns
one q block (block_q x D) and loops over kv blocks with a fori_loop carrying
the running (max, denom, acc) — KV is read once per (group, q-block), never
materialized at Hq (the grouped-query memory win). Causal programs skip kv
blocks above the diagonal (the classic ~2x flop win).

VMEM per program at (block_q=512, block_k=512, D=128):
q 256 KiB + kv block 2x256 KiB + p 1 MiB + acc 256 KiB ~= 2 MiB,
double-bufferable inside the ~128 MiB v5e VMEM.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.quantize_em import ref as _qref

NEG_INF = -1e30


def _attn_kernel(*refs, scale, block_q, block_k, seq_len, causal, window,
                 quantized=False):
    if quantized:
        # fused epilogue: the (4,) int32 runtime format row arrives first,
        # as an SMEM scalar-prefetch operand (same vector the standalone
        # quantize_em kernel prefetches)
        fmt_ref, q_ref, k_ref, v_ref, o_ref = refs
    else:
        q_ref, k_ref, v_ref, o_ref = refs
    qb = pl.program_id(2)
    q = q_ref[0, 0].astype(jnp.float32) * scale            # (block_q, D)

    nk = seq_len // block_k

    def body(kb, carry):
        m, l, acc = carry
        k = k_ref[0, kb].astype(jnp.float32)               # (block_k, D)
        v = v_ref[0, kb].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        qi = qb * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        ki = kb * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = jnp.ones(s.shape, jnp.bool_)
        if causal:
            mask &= qi >= ki
        if window is not None:
            mask &= (qi - ki) < window
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[:, None] + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    m0 = jnp.full((block_q,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q,), jnp.float32)
    a0 = jnp.zeros((block_q, v_ref.shape[-1]), jnp.float32)
    # causal: only kv blocks intersecting the lower triangle
    nk_eff = ((qb + 1) * block_q + block_k - 1) // block_k if causal else nk
    m, l, acc = jax.lax.fori_loop(0, nk_eff, body, (m0, l0, a0))
    out = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)
    if quantized:
        # quantize the *stored* value (post output-dtype cast) so the fused
        # kernel is bit-identical to unfused kernel + quantize_dynamic
        out = _qref.quantize_epilogue(out, fmt_ref)
    o_ref[0, 0] = out


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "scale", "block_q", "block_k",
                     "interpret"))
def flash_attention_pallas(q, k, v, *, causal: bool = True, window=None,
                           scale=None, block_q: int = 512, block_k: int = 512,
                           interpret: bool = False, out_fmt=None):
    """q: (B, Hq, S, D); k/v: (B, Hkv, S, D/Dv). Returns (B, Hq, S, Dv).

    ``out_fmt`` (optional): a (4,) int32 runtime format row
    (exp_bits, man_bits, saturate, ieee_inf | fault << 1). When given, the
    dynamic quantize runs as a fused epilogue on the output store — one
    kernel instead of kernel + separate quantize pass — and the row is
    runtime *data* (scalar prefetch), so swapping formats never recompiles.
    """
    B, Hq, S, D = q.shape
    Hkv = k.shape[1]
    Dv = v.shape[-1]
    G = Hq // Hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    assert S % block_q == 0 and S % block_k == 0, (S, block_q, block_k)

    qr = q.reshape(B * Hkv, G, S, D)
    kr = k.reshape(B * Hkv, S // block_k, block_k, D)
    vr = v.reshape(B * Hkv, S // block_k, block_k, Dv)
    grid = (B * Hkv, G, S // block_q)

    kernel = functools.partial(_attn_kernel, scale=scale, block_q=block_q,
                               block_k=block_k, seq_len=S, causal=causal,
                               window=window, quantized=out_fmt is not None)
    in_blocks = [
        ((1, 1, block_q, D), lambda bh, g, qb: (bh, g, qb, 0)),
        ((1, S // block_k, block_k, D), lambda bh, g, qb: (bh, 0, 0, 0)),
        ((1, S // block_k, block_k, Dv), lambda bh, g, qb: (bh, 0, 0, 0)),
    ]
    out_block = ((1, 1, block_q, Dv), lambda bh, g, qb: (bh, g, qb, 0))
    out_shape = jax.ShapeDtypeStruct((B * Hkv, G, S, Dv), q.dtype)

    # index maps take the trailing prefetch ref when there is one
    prefetch = () if out_fmt is None else (jnp.asarray(out_fmt, jnp.int32),)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=grid,
        in_specs=[pl.BlockSpec(b, lambda bh, g, qb, *_, ix=ix: ix(bh, g, qb))
                  for b, ix in in_blocks],
        out_specs=pl.BlockSpec(
            out_block[0],
            lambda bh, g, qb, *_, ix=out_block[1]: ix(bh, g, qb)),
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        name="flash_attention",
        out_shape=out_shape,
        interpret=interpret,
    )(*prefetch, qr, kr, vr)
    return out.reshape(B, Hq, S, Dv)
