"""Attention: GQA/MQA with RoPE/M-RoPE/partial-RoPE, sliding windows, MLA.

Training/prefill use a chunked, memory-bounded flash attention (pure jnp
scan over KV blocks with running max/denominator; the Pallas TPU kernel in
``repro.kernels.flash_attention`` implements the same contract and is
selected on TPU via ``repro.kernels.flash_attention.ops``). KV heads are
never materialized to Hq (grouped einsum) — a deliberate memory optimization
over the naive repeat-KV formulation.

Decode paths attend one new token against a pre-allocated cache; MLA decode
uses the absorbed low-rank form so the cache stays (kv_lora + rope_dim) wide.
"""
from __future__ import annotations

import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ArchConfig
from repro.distributed.sharding import constrain, mesh_active
from repro.kernels import kv_write
from repro.models import common
from repro.models.common import ParamDef


NEG_INF = -1e30


# ---------------------------------------------------------------------------
# chunked flash attention (jnp reference; contract shared with Pallas kernel)
# ---------------------------------------------------------------------------

def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None, scale: Optional[float] = None,
                    q_chunk: int = 1024, kv_chunk: int = 1024):
    """q: (B, Hq, S, Dk); k: (B, Hkv, S, Dk); v: (B, Hkv, S, Dv).
    Grouped-query: Hq % Hkv == 0. Returns (B, Hq, S, Dv)."""
    B, Hq, S, Dk = q.shape
    Hkv = k.shape[1]
    Dv = v.shape[-1]
    G = Hq // Hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(Dk)

    q_chunk = min(q_chunk, S)
    kv_chunk = min(kv_chunk, S)
    nq, nk = -(-S // q_chunk), -(-S // kv_chunk)
    assert S % q_chunk == 0 and S % kv_chunk == 0, (S, q_chunk, kv_chunk)

    qg = q.reshape(B, Hkv, G, S, Dk)
    qs = qg.reshape(B, Hkv, G, nq, q_chunk, Dk).transpose(3, 0, 1, 2, 4, 5)
    ks = k.reshape(B, Hkv, nk, kv_chunk, Dk).transpose(2, 0, 1, 3, 4)
    vs = v.reshape(B, Hkv, nk, kv_chunk, Dv).transpose(2, 0, 1, 3, 4)

    q_pos = jnp.arange(S).reshape(nq, q_chunk)
    k_pos = jnp.arange(S).reshape(nk, kv_chunk)

    # sliding-window block skipping: with a static window each q chunk only
    # needs the kv chunks covering [q0 - window + 1, q0 + Cq) — an O(S*W)
    # instead of O(S^2) schedule (the Pallas kernel additionally skips
    # above-diagonal blocks for plain causal).
    n_win = nk
    if causal and isinstance(window, int):
        n_win = min(nk, (window + q_chunk - 1 + kv_chunk - 1) // kv_chunk + 1)

    def per_q_chunk(carry, qc):
        del carry
        q_blk, qp = qc  # (B,Hkv,G,Cq,Dk), (Cq,)

        if n_win < nk:
            start = jnp.clip((qp[0] - (window - 1)) // kv_chunk, 0, nk - n_win)
            ks_l = lax.dynamic_slice_in_dim(ks, start, n_win, axis=0)
            vs_l = lax.dynamic_slice_in_dim(vs, start, n_win, axis=0)
            kp_l = lax.dynamic_slice_in_dim(k_pos, start, n_win, axis=0)
        else:
            ks_l, vs_l, kp_l = ks, vs, k_pos

        def per_kv_chunk(state, kc):
            m, l, acc = state
            k_blk, v_blk, kp = kc
            s = jnp.einsum("bhgqd,bhkd->bhgqk", q_blk.astype(jnp.float32),
                           k_blk.astype(jnp.float32)) * scale
            mask = jnp.ones((qp.shape[0], kp.shape[0]), bool)
            if causal:
                mask &= qp[:, None] >= kp[None, :]
            if window is not None:
                mask &= (qp[:, None] - kp[None, :]) < window
            s = jnp.where(mask[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhgqk,bhkd->bhgqd", p, v_blk.astype(jnp.float32))
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, Hkv, G, q_blk.shape[-2]), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, Hkv, G, q_blk.shape[-2]), jnp.float32)
        a0 = jnp.zeros((B, Hkv, G, q_blk.shape[-2], Dv), jnp.float32)
        # flash-style backward: recompute the (Cq,Ck) score/prob blocks in
        # the bwd pass instead of storing them per chunk pair (autodiff of a
        # plain scan would save every p matrix — the dominant train-memory
        # term; see EXPERIMENTS.md §Perf)
        per_kv = jax.checkpoint(per_kv_chunk, prevent_cse=False)
        (m, l, acc), _ = lax.scan(per_kv, (m0, l0, a0),
                                  (ks_l, vs_l, kp_l))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return None, out

    per_q = jax.checkpoint(per_q_chunk, prevent_cse=False)
    _, outs = lax.scan(per_q, None, (qs, q_pos))
    # outs: (nq, B, Hkv, G, Cq, Dv) -> (B, Hq, S, Dv)
    out = outs.transpose(1, 2, 3, 0, 4, 5).reshape(B, Hq, S, Dv)
    return out.astype(v.dtype)


def decode_attention(q, k_cache, v_cache, pos, *, window: Optional[int] = None,
                     scale: Optional[float] = None):
    """One-token attention. q: (B, Hq, Dk); caches: (B, Hkv, S, D*);
    pos: int32 scalar or (B,) vector — per-slot count of valid cache
    entries. A scalar means every batch lane sits at the same cursor."""
    B, Hq, Dk = q.shape
    Hkv, S = k_cache.shape[1], k_cache.shape[2]
    G = Hq // Hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(Dk)
    pos_b = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))

    qg = q.reshape(B, Hkv, G, Dk)
    s = jnp.einsum("bhgd,bhkd->bhgk", qg.astype(jnp.float32),
                   k_cache.astype(jnp.float32)) * scale
    idx = jnp.arange(S)[None, None, None, :]
    cur = pos_b[:, None, None, None]
    valid = idx < cur
    if window is not None:
        valid &= idx >= (cur - window)
    s = jnp.where(valid, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgk,bhkd->bhgd", p, v_cache.astype(jnp.float32))
    return out.reshape(B, Hq, -1).astype(v_cache.dtype)


def decode_attention_with_new(q, k_cache, v_cache, k_new, v_new, pos, *,
                              window: Optional[int] = None,
                              ring: bool = False):
    """One-token attention over the cache as it stands BEFORE the new
    token's write, plus the new token itself: the same entries, and the
    same result up to summation order, as ``decode_attention`` over the
    written cache, without writing it.

    q: (B, Hq, Dk); caches: (B, Hkv, S, D*); k_new/v_new: (B, Hkv, D*),
    already in the cache dtype. pos: (B,) count of tokens in each slot
    before this one (the new token's position).

    Non-ring: old entries ``idx < pos`` (and inside the window) count; a
    cursor past the cache end would write nothing, so its new token does
    not count either. ``ring=True`` (S == window): slot ``pos mod S`` holds
    the token about to fall out of the window, so it is masked; the other
    slots count once written (slot s holds position pos - ((pos - s) mod
    S), negative while unwritten)."""
    B, Hq, Dk = q.shape
    Hkv, S = k_cache.shape[1], k_cache.shape[2]
    G = Hq // Hkv
    f32 = jnp.float32
    scale = 1.0 / math.sqrt(Dk)

    qg = q.reshape(B, Hkv, G, Dk).astype(f32)
    s = jnp.einsum("bhgd,bhkd->bhgk", qg, k_cache.astype(f32)) * scale
    s_new = jnp.einsum("bhgd,bhd->bhg", qg, k_new.astype(f32)) * scale
    idx = jnp.arange(S)[None, None, None, :]
    cur = pos[:, None, None, None]
    if ring:
        age = jnp.mod(cur - idx, S)          # 0: the slot being overwritten
        valid = (age > 0) & (cur - age >= 0)
        new_valid = jnp.ones((B,), bool)
    else:
        valid = idx < cur
        if window is not None:
            valid &= idx > cur - window
        new_valid = pos < S
    new_valid = new_valid[:, None, None]
    s = jnp.where(valid, s, NEG_INF)
    s_new = jnp.where(new_valid, s_new, NEG_INF)
    m = jnp.maximum(jnp.max(s, axis=-1), s_new)
    p = jnp.exp(s - m[..., None])
    p_new = jnp.where(new_valid, jnp.exp(s_new - m), 0.0)
    denom = jnp.sum(p, axis=-1) + p_new
    out = (jnp.einsum("bhgk,bhkd->bhgd", p, v_cache.astype(f32))
           + p_new[..., None] * v_new.astype(f32)[:, :, None])
    out = out / denom[..., None]
    return out.reshape(B, Hq, -1).astype(v_cache.dtype)


# ---------------------------------------------------------------------------
# GQA block
# ---------------------------------------------------------------------------

def gqa_param_defs(cfg: ArchConfig) -> dict:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    H, Hkv = cfg.n_heads, cfg.n_kv_heads
    scale = 0.02
    o_scale = 0.02 / math.sqrt(2 * cfg.n_layers)
    defs = {
        "wq": ParamDef((d, H * hd), ("embed", "heads"), scale=scale),
        "wk": ParamDef((d, Hkv * hd), ("embed", "kv_heads"), scale=scale),
        "wv": ParamDef((d, Hkv * hd), ("embed", "kv_heads"), scale=scale),
        "wo": ParamDef((H * hd, d), ("heads", "embed"), scale=o_scale),
    }
    if cfg.qkv_bias:
        defs["bq"] = ParamDef((H * hd,), ("heads",), init="zeros")
        defs["bk"] = ParamDef((Hkv * hd,), ("kv_heads",), init="zeros")
        defs["bv"] = ParamDef((Hkv * hd,), ("kv_heads",), init="zeros")
    return defs


def _project_qkv(p, x, cfg: ArchConfig, positions):
    B, S, _ = x.shape
    hd = cfg.resolved_head_dim
    H, Hkv = cfg.n_heads, cfg.n_kv_heads
    q = x @ p["wq"].astype(x.dtype)
    k = x @ p["wk"].astype(x.dtype)
    v = x @ p["wv"].astype(x.dtype)
    if cfg.qkv_bias:
        q = q + p["bq"].astype(x.dtype)
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    q = q.reshape(B, S, H, hd).transpose(0, 2, 1, 3)
    k = k.reshape(B, S, Hkv, hd).transpose(0, 2, 1, 3)
    v = v.reshape(B, S, Hkv, hd).transpose(0, 2, 1, 3)
    if cfg.rope_type == "rope":
        q = common.apply_rope(q, positions, theta=cfg.rope_theta,
                              fraction=cfg.rope_fraction)
        k = common.apply_rope(k, positions, theta=cfg.rope_theta,
                              fraction=cfg.rope_fraction)
    elif cfg.rope_type == "mrope":
        q = common.apply_mrope(q, positions, theta=cfg.rope_theta,
                               sections=cfg.mrope_sections)
        k = common.apply_mrope(k, positions, theta=cfg.rope_theta,
                               sections=cfg.mrope_sections)
    return q, k, v


def gqa_forward(p, x, cfg: ArchConfig, *, positions, causal: bool = True,
                window: Optional[int] = None):
    """Training/prefill attention. x: (B, S, d). Returns ((B,S,d), kv)."""
    B, S, _ = x.shape
    with jax.named_scope("qkv"):
        q, k, v = _project_qkv(p, x, cfg, positions)
        q = constrain(q, "batch", "heads", "seq", None)
        k = constrain(k, "batch", "kv_heads", "seq", None)
        v = constrain(v, "batch", "kv_heads", "seq", None)
    with jax.named_scope("mix"):
        o = flash_attention(q, k, v, causal=causal, window=window)
        o = constrain(o, "batch", "heads", "seq", None)
    with jax.named_scope("proj"):
        o = o.transpose(0, 2, 1, 3).reshape(B, S, -1)
        out = o @ p["wo"].astype(x.dtype)
        out = constrain(out, "batch", "seq", "embed")
    return out, (k, v)


def _cache_slot(pos_b, S_cache: int, window: Optional[int]):
    """(slot each lane writes, whether the cache is a ring): a ring cache
    (S == window) writes at pos mod S, any other at pos itself."""
    ring = window is not None and S_cache == window
    return (jnp.mod(pos_b, S_cache) if ring else pos_b), ring


def gqa_decode_rows(p, x1, cache, pos, cfg: ArchConfig, *,
                    window: Optional[int] = None, positions3=None):
    """Decode attention that reads the layer's cache without writing it.
    x1: (B, 1, d); cache: dict(k=(B,Hkv,S,hd), v=...); pos: scalar or (B,)
    count of tokens already in each slot's cache. Returns (out (B, 1, d),
    rows): rows is dict(k=, v=) of the new token's (B, Hkv, hd), in the
    cache dtype, for :func:`gqa_write` (or a stacked write) to store."""
    B = x1.shape[0]
    S_cache = cache["k"].shape[2]
    pos_b = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    if cfg.rope_type == "mrope" and positions3 is None:
        positions3 = jnp.broadcast_to(pos_b[None, :, None], (3, B, 1))
    positions = pos_b[:, None]
    q, k, v = _project_qkv(
        p, x1, cfg, positions3 if cfg.rope_type == "mrope" else positions)
    k_new = k[:, :, 0].astype(cache["k"].dtype)
    v_new = v[:, :, 0].astype(cache["v"].dtype)
    _, ring = _cache_slot(pos_b, S_cache, window)
    o = decode_attention_with_new(q[:, :, 0], cache["k"], cache["v"],
                                  k_new, v_new, pos_b,
                                  window=None if ring else window, ring=ring)
    out = o.reshape(B, 1, -1) @ p["wo"].astype(x1.dtype)
    return out, {"k": k_new, "v": v_new}


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def gqa_write(cache, rows, pos, *, window: Optional[int] = None):
    """Write each lane's new K/V row into the cache at its slot: one layer's
    cache (B,Hkv,S,hd) with rows (B,Hkv,hd), or the stacked (L,B,Hkv,S,hd)
    cache with every layer's rows (L,B,Hkv,hd). A non-ring cursor past the
    cache end writes nothing (dead serving lanes).

    In place when the cache is donated. On TPU (S a multiple of 128) the
    ``kv_write`` kernel rewrites the 128-slot block that holds each row;
    elsewhere one ``dynamic_update_slice`` a lane. A scatter, or a write
    that reads its slot back, has XLA on TPU copy the whole cache into
    another layout and back around it. Under a mesh the cache is sharded
    (slots, and the sequence for context parallelism), where an update at
    a dynamic sequence index gathers the cache: there a select over it
    stays local to each shard."""
    k = cache["k"]
    ax = k.ndim - 4                      # the batch axis
    B, S_cache = k.shape[ax], k.shape[-2]
    pos_b = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    slot, ring = _cache_slot(pos_b, S_cache, window)
    if mesh_active():
        hit = (jnp.arange(S_cache) == slot[:, None])[:, None, :, None]
        return {n: jnp.where(hit, jnp.expand_dims(rows[n], -2), cache[n])
                for n in ("k", "v")}
    if _on_tpu() and S_cache % kv_write.LANES == 0:
        # the kernel takes a stacked cache, sequence last: a bitcast on TPU
        def stack(t):
            return t if ax else t[None]

        out = kv_write.kv_write(
            *(jnp.swapaxes(stack(cache[n]), -1, -2) for n in ("k", "v")),
            *(stack(rows[n]) for n in ("k", "v")), slot)
        return {n: jnp.swapaxes(o, -1, -2).reshape(cache[n].shape)
                for n, o in zip(("k", "v"), out)}
    out = {}
    for n in ("k", "v"):
        c, r = cache[n], rows[n]
        if not ring:
            # the write clamps a cursor past the end to the last entry:
            # write back what that entry holds
            dead = (pos_b >= S_cache)[:, None, None]
            r = jnp.where(dead, c[..., S_cache - 1, :], r)
        for b in range(B):
            upd = jnp.expand_dims(lax.index_in_dim(r, b, ax), -2)
            c = lax.dynamic_update_slice(c, upd,
                                         (0,) * ax + (b, 0, slot[b], 0))
        out[n] = c
    return out


def gqa_decode(p, x1, cache, pos, cfg: ArchConfig, *,
               window: Optional[int] = None, positions3=None):
    """x1: (B, 1, d); cache: dict(k=(B,Hkv,S,hd), v=...). pos: scalar or
    (B,) count of tokens already in each slot's cache (ragged decode writes
    each lane at its own cursor). When the cache was allocated ring-sized
    (S == window < requested seq_len) the slot is pos mod S; a non-ring
    cursor past the cache end simply doesn't write (dead serving lanes).
    Returns (out, updated layer cache)."""
    out, rows = gqa_decode_rows(p, x1, cache, pos, cfg, window=window,
                                positions3=positions3)
    return out, gqa_write(cache, rows, pos, window=window)


def gqa_init_cache(cfg: ArchConfig, batch: int, seq_len: int, dtype,
                   window: Optional[int] = None):
    """``window``: allocate a ring buffer of that size instead of the full
    sequence (sliding-window layers never need more — the long_500k memory
    win, EXPERIMENTS.md §Perf iteration 13)."""
    hd = cfg.resolved_head_dim
    S = min(seq_len, window) if window else seq_len
    shape = (batch, cfg.n_kv_heads, S, hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


# ---------------------------------------------------------------------------
# Multi-head Latent Attention (DeepSeek-V2)
# ---------------------------------------------------------------------------

def mla_param_defs(cfg: ArchConfig) -> dict:
    m = cfg.mla
    d, H = cfg.d_model, cfg.n_heads
    qk = m.nope_head_dim + m.rope_head_dim
    o_scale = 0.02 / math.sqrt(2 * cfg.n_layers)
    if m.q_lora is None:
        q = {"q_proj": ParamDef((d, H * qk), ("embed", "heads"))}
    else:
        q = {"q_down": ParamDef((d, m.q_lora), ("embed", None)),
             "q_norm": ParamDef((m.q_lora,), (None,), init="ones"),
             "q_up": ParamDef((m.q_lora, H * qk), (None, "heads"))}
    return {
        **q,
        "kv_down": ParamDef((d, m.kv_lora + m.rope_head_dim), ("embed", None)),
        "kv_norm": ParamDef((m.kv_lora,), (None,), init="ones"),
        "kv_up": ParamDef((m.kv_lora, H * (m.nope_head_dim + m.v_head_dim)),
                          (None, "heads")),
        "wo": ParamDef((H * m.v_head_dim, d), ("heads", "embed"), scale=o_scale),
    }


def mla_softmax_scale(cfg: ArchConfig) -> float:
    """1/sqrt(q head size), times YaRN's mscale(factor, mscale_all_dim)^2
    where the configuration scales its RoPE (DeepSeek-V2's softmax_scale)."""
    m = cfg.mla
    scale = 1.0 / math.sqrt(m.nope_head_dim + m.rope_head_dim)
    sc = cfg.rope_scaling
    if sc is not None and sc.mscale_all_dim:
        scale *= common.yarn_mscale(sc.factor, sc.mscale_all_dim) ** 2
    return scale


def _mla_rope(x, positions, cfg):
    return common.apply_rope(x, positions, theta=cfg.rope_theta,
                             scaling=cfg.rope_scaling)


def _mla_q(p, x, cfg, positions):
    m = cfg.mla
    B, S, _ = x.shape
    H = cfg.n_heads
    if m.q_lora is None:
        q = x @ p["q_proj"].astype(x.dtype)
    else:
        cq = common.rmsnorm(x @ p["q_down"].astype(x.dtype), p["q_norm"],
                            cfg.norm_eps)
        q = cq @ p["q_up"].astype(x.dtype)
    q = q.reshape(B, S, H, m.nope_head_dim + m.rope_head_dim).transpose(
        0, 2, 1, 3)
    q_nope, q_rope = q[..., :m.nope_head_dim], q[..., m.nope_head_dim:]
    return q_nope, _mla_rope(q_rope, positions, cfg)


def _mla_latent(p, x, cfg, positions):
    m = cfg.mla
    kv = x @ p["kv_down"].astype(x.dtype)
    c_kv, k_rope = kv[..., :m.kv_lora], kv[..., m.kv_lora:]
    c_kv = common.rmsnorm(c_kv, p["kv_norm"], cfg.norm_eps)
    k_rope = _mla_rope(k_rope[:, None], positions, cfg)[:, 0]
    return c_kv, k_rope          # (B,S,kv_lora), (B,S,rope_dim)


def mla_forward(p, x, cfg: ArchConfig, *, positions):
    """Training/prefill MLA in the expanded form."""
    m = cfg.mla
    B, S, _ = x.shape
    H = cfg.n_heads
    with jax.named_scope("mla_qkv"):
        q_nope, q_rope = _mla_q(p, x, cfg, positions)
        c_kv, k_rope = _mla_latent(p, x, cfg, positions)
        kv = (c_kv @ p["kv_up"].astype(x.dtype)).reshape(
            B, S, H, m.nope_head_dim + m.v_head_dim).transpose(0, 2, 1, 3)
        k_nope, v = kv[..., :m.nope_head_dim], kv[..., m.nope_head_dim:]
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope[:, None],
                                      (B, H, S, m.rope_head_dim))], axis=-1)
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        q = constrain(q, "batch", "heads", "seq", None)
        k = constrain(k, "batch", "heads", "seq", None)
        v = constrain(v, "batch", "heads", "seq", None)
    with jax.named_scope("mla_mix"):
        o = flash_attention(q, k, v, causal=True,
                            scale=mla_softmax_scale(cfg))
    with jax.named_scope("mla_proj"):
        o = o.transpose(0, 2, 1, 3).reshape(B, S, H * m.v_head_dim)
        out = o @ p["wo"].astype(x.dtype)
    return out, (c_kv, k_rope)


def mla_decode_rows(p, x1, cache, pos, cfg: ArchConfig):
    """Absorbed-form decode that reads the layer's latent cache without
    writing it: attention over the cache's first ``pos`` rows plus the new
    token's own row (none for a cursor past the end), as
    ``decode_attention_with_new`` does for GQA. x1: (B, 1, d); cache:
    dict(c_kv=(B,S,kv_lora), k_rope=(B,S,rope_dim)); pos: scalar or (B,).
    Returns (out (B, 1, d), rows): the new token's latent rows, in the
    cache dtype, for :func:`mla_write` to store."""
    m = cfg.mla
    B = x1.shape[0]
    H = cfg.n_heads
    S = cache["c_kv"].shape[-2]
    dt = cache["c_kv"].dtype
    f32 = jnp.float32
    pos_b = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    positions = pos_b[:, None]
    with jax.named_scope("mla"):
        with jax.named_scope("absorb"):
            q_nope, q_rope = _mla_q(p, x1, cfg, positions)  # (B,H,1,dn|dr)
            c_new, kr_new = _mla_latent(p, x1, cfg, positions)
            c_new = c_new[:, 0].astype(dt)                  # (B, kv_lora)
            kr_new = kr_new[:, 0].astype(cache["k_rope"].dtype)
            # kv_up columns interleave [nope | v] per head
            w_up = p["kv_up"].reshape(m.kv_lora, H,
                                      m.nope_head_dim + m.v_head_dim)
            w_uk = w_up[..., :m.nope_head_dim]
            w_uv = w_up[..., m.nope_head_dim:]
            # absorb W_uk into q: (B,H,dn) x (kv_lora,H,dn) -> (B,H,kv_lora)
            q_lat = jnp.einsum("bhd,lhd->bhl", q_nope[:, :, 0].astype(f32),
                               w_uk.astype(f32)).astype(dt)
            q_pe = q_rope[:, :, 0].astype(dt)
        with jax.named_scope("attend"):
            # the cache is read in its own dtype, accumulated in f32
            scale = mla_softmax_scale(cfg)
            s = (jnp.einsum("bhl,bsl->bhs", q_lat, cache["c_kv"],
                            preferred_element_type=f32)
                 + jnp.einsum("bhd,bsd->bhs", q_pe, cache["k_rope"],
                              preferred_element_type=f32)) * scale
            s_new = (jnp.einsum("bhl,bl->bh", q_lat, c_new,
                                preferred_element_type=f32)
                     + jnp.einsum("bhd,bd->bh", q_pe, kr_new,
                                  preferred_element_type=f32)) * scale
            valid = jnp.arange(S)[None, None, :] < pos_b[:, None, None]
            new_valid = (pos_b < S)[:, None]
            s = jnp.where(valid, s, NEG_INF)
            s_new = jnp.where(new_valid, s_new, NEG_INF)
            mx = jnp.maximum(jnp.max(s, axis=-1), s_new)
            pr = jnp.exp(s - mx[..., None])
            p_new = jnp.where(new_valid, jnp.exp(s_new - mx), 0.0)
            denom = jnp.sum(pr, axis=-1) + p_new
            # the new row's weight is rounded as the cached rows' are
            ctx = (jnp.einsum("bhs,bsl->bhl", pr.astype(dt), cache["c_kv"],
                              preferred_element_type=f32)
                   + p_new.astype(dt).astype(f32)[..., None]
                   * c_new.astype(f32)[:, None])
            ctx = ctx / denom[..., None]
            o = jnp.einsum("bhl,lhd->bhd", ctx, w_uv.astype(f32))
            out = o.reshape(B, 1, H * m.v_head_dim).astype(x1.dtype) \
                @ p["wo"].astype(x1.dtype)
    return out, {"c_kv": c_new, "k_rope": kr_new}


def mla_write(cache, rows, pos):
    """Write each lane's new latent rows into the cache at its cursor: one
    layer's cache (B,S,D) with rows (B,D), or the stacked (L,B,S,D) cache
    with every layer's rows (L,B,D), for both leaves. A cursor past the
    cache end writes nothing (dead serving lanes).

    In place when the cache is donated: one ``dynamic_update_slice`` a lane
    and leaf. ``c_kv`` (512 wide) keeps the sequence on sublanes on TPU, so
    a row is one sublane of its tiles; ``k_rope`` (64 wide) keeps the
    sequence minor, so a row is one lane of four tiles a layer. Under a
    mesh a select over the cache, which stays local to each shard, as in
    :func:`gqa_write`."""
    with jax.named_scope("mla"), jax.named_scope("write"):
        c0 = cache["c_kv"]
        ax = c0.ndim - 3                     # the batch axis
        B, S = c0.shape[ax], c0.shape[-2]
        pos_b = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
        if mesh_active():
            hit = (jnp.arange(S) == pos_b[:, None])[..., None]
            return {n: jnp.where(hit, jnp.expand_dims(rows[n], -2), cache[n])
                    for n in ("c_kv", "k_rope")}
        dead = (pos_b >= S)[:, None]
        out = {}
        for n in ("c_kv", "k_rope"):
            c = cache[n]
            # the write clamps a cursor past the end to the last row:
            # write back what that row holds
            r = jnp.where(dead, c[..., S - 1, :], rows[n])
            for b in range(B):
                upd = jnp.expand_dims(lax.index_in_dim(r, b, ax), -2)
                c = lax.dynamic_update_slice(c, upd,
                                             (0,) * ax + (b, pos_b[b], 0))
            out[n] = c
        return out


def mla_decode(p, x1, cache, pos, cfg: ArchConfig):
    """Absorbed-form decode: cache holds only (c_kv, k_rope). pos: scalar
    or (B,) per-slot cursor, matching ``gqa_decode``. Returns (out, the
    written layer cache)."""
    out, rows = mla_decode_rows(p, x1, cache, pos, cfg)
    return out, mla_write(cache, rows, pos)


def mla_init_cache(cfg: ArchConfig, batch: int, seq_len: int, dtype):
    m = cfg.mla
    return {
        "c_kv": jnp.zeros((batch, seq_len, m.kv_lora), dtype),
        "k_rope": jnp.zeros((batch, seq_len, m.rope_head_dim), dtype),
    }
