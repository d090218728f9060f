"""Decoder stack: embeds -> scanned layers -> norm -> logits.

One generic implementation hosts all assigned decoder families:
  * dense GQA (glm4, deepseek-coder, internlm2, h2o-danube/SWA, qwen2-vl/M-RoPE)
  * MoE (olmoe; deepseek-v2 and -v2-lite with MLA + shared experts + leading
    dense layers; a layer may hold a share of the experts, see models/moe.py)
  * hybrid (hymba: parallel GQA-SWA + Mamba heads per layer, 3 global layers)
  * attn-free (rwkv6: time-mix + channel-mix)

Layers are stacked into a single (L, ...) param pytree and executed with
``lax.scan`` (+ per-layer remat) so the HLO stays compact at 60+ layers;
heterogeneous per-layer behaviour (sliding-window vs global attention) rides
the scan as a traced flag array. Every module is wrapped in
``jax.named_scope`` — these names are the truncation-policy surface of the
profiling engine (core/policy.py).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ArchConfig
from repro.distributed.sharding import constrain
from repro.models import attention, moe as moe_mod, ssm
from repro.models.common import (
    ParamDef, ACTIVATIONS, rmsnorm, layernorm, init_tree, abstract_tree,
    axes_tree, count_params,
)


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------

def mlp_param_defs(cfg: ArchConfig, d_ff: int) -> dict:
    d = cfg.d_model
    o_scale = 0.02 / math.sqrt(2 * cfg.n_layers)
    mult = 2 if cfg.act == "swiglu" else 1
    return {
        "wi": ParamDef((d, mult * d_ff), ("embed", "mlp")),
        "wo": ParamDef((d_ff, d), ("mlp", "embed"), scale=o_scale),
    }


def mlp_forward(p, x, cfg: ArchConfig):
    with jax.named_scope("mlp"):
        h = x @ p["wi"].astype(x.dtype)
        h = constrain(h, "batch", "seq", "mlp")
        h = ACTIVATIONS[cfg.act](h)
        out = h @ p["wo"].astype(x.dtype)
        return constrain(out, "batch", "seq", "embed")


# ---------------------------------------------------------------------------
# norm dispatch
# ---------------------------------------------------------------------------

def norm_defs(cfg: ArchConfig) -> dict:
    if cfg.norm == "layernorm":
        return {"scale": ParamDef((cfg.d_model,), ("embed",), init="ones"),
                "bias": ParamDef((cfg.d_model,), ("embed",), init="zeros")}
    return {"scale": ParamDef((cfg.d_model,), ("embed",), init="ones")}


def apply_norm(p, x, cfg: ArchConfig):
    if cfg.norm == "layernorm":
        return layernorm(x, p["scale"], p["bias"], cfg.norm_eps)
    return rmsnorm(x, p["scale"], cfg.norm_eps)


# ---------------------------------------------------------------------------
# layer definitions
# ---------------------------------------------------------------------------

def layer_param_defs(cfg: ArchConfig, kind: str) -> dict:
    """kind: 'dense' | 'moe' — which feed-forward the layer carries."""
    defs: Dict[str, Any] = {"norm1": norm_defs(cfg), "norm2": norm_defs(cfg)}
    if cfg.attn_type == "gqa":
        defs["attn"] = attention.gqa_param_defs(cfg)
    elif cfg.attn_type == "mla":
        defs["attn"] = attention.mla_param_defs(cfg)
    elif cfg.attn_type == "hymba":
        defs["attn"] = attention.gqa_param_defs(cfg)
        defs["mamba"] = ssm.mamba_param_defs(cfg)
        defs["branch_norm_attn"] = ParamDef((cfg.d_model,), ("embed",), init="ones")
        defs["branch_norm_ssm"] = ParamDef((cfg.d_model,), ("embed",), init="ones")
        defs["branch_beta"] = ParamDef((2,), (None,), init="ones")
    elif cfg.attn_type == "rwkv6":
        defs["time_mix"] = ssm.rwkv6_param_defs(cfg)
    else:
        raise ValueError(cfg.attn_type)

    if cfg.attn_type == "rwkv6":
        defs["channel_mix"] = ssm.rwkv6_channel_defs(cfg)
    elif kind == "moe":
        defs["moe"] = moe_mod.moe_param_defs(cfg)
    else:
        d_ff = cfg.moe.d_ff_dense if (cfg.moe and cfg.moe.d_ff_dense and
                                      kind == "dense_lead") else cfg.d_ff
        defs["mlp"] = mlp_param_defs(cfg, d_ff)
    return defs


def _seq_mix(cfg: ArchConfig, p, x, positions, is_global, mix_state,
             decode: bool, pos, rows_only: bool = False):
    """Dispatch the sequence-mixing block. Returns (y, new_mix_state).
    ``is_global=True`` lifts the sliding window (global-attention layers are
    executed as their own unrolled segments so the window stays static and
    the flash path can skip out-of-window KV blocks). ``rows_only`` (GQA
    and MLA decode): leave the cache unwritten and return the new token's
    K/V (or latent) rows in place of the new state, for one stacked write
    after the scan."""
    if cfg.attn_type == "gqa":
        window = None if is_global else cfg.sliding_window
        if decode:
            decode_fn = (attention.gqa_decode_rows if rows_only
                         else attention.gqa_decode)
            return decode_fn(p["attn"], x, mix_state, pos, cfg,
                             window=window)
        with jax.named_scope("attn"):
            y, _ = attention.gqa_forward(p["attn"], x, cfg, positions=positions,
                                         window=window)
        return y, mix_state

    if cfg.attn_type == "mla":
        if decode:
            decode_fn = (attention.mla_decode_rows if rows_only
                         else attention.mla_decode)
            return decode_fn(p["attn"], x, mix_state, pos, cfg)
        with jax.named_scope("attn"):
            y, _ = attention.mla_forward(p["attn"], x, cfg, positions=positions)
        return y, mix_state

    if cfg.attn_type == "hymba":
        window = None if is_global else cfg.sliding_window
        if decode:
            ya, kv = attention.gqa_decode(p["attn"], x, mix_state["kv"], pos,
                                          cfg, window=window)
            ym, ms = ssm.mamba_decode(p["mamba"], x, mix_state["mamba"], cfg)
            new_state = {"kv": kv, "mamba": ms}
        else:
            with jax.named_scope("attn"):
                ya, _ = attention.gqa_forward(p["attn"], x, cfg,
                                              positions=positions, window=window)
            with jax.named_scope("mamba"):
                ym, _ = ssm.mamba_forward(p["mamba"], x, cfg)
            new_state = mix_state
        ya = rmsnorm(ya, p["branch_norm_attn"], cfg.norm_eps)
        ym = rmsnorm(ym, p["branch_norm_ssm"], cfg.norm_eps)
        beta = p["branch_beta"].astype(x.dtype)
        return 0.5 * (beta[0] * ya + beta[1] * ym), new_state

    if cfg.attn_type == "rwkv6":
        with jax.named_scope("time_mix"):
            if decode:
                y, x_last, s = ssm._rwkv6_mix(
                    p["time_mix"], x, mix_state["tm_shift"], cfg,
                    mix_state["tm_state"])
                new_state = dict(mix_state, tm_shift=x_last, tm_state=s)
                return y, new_state
            B = x.shape[0]
            x_prev = jnp.zeros((B, 1, x.shape[-1]), x.dtype)
            s0 = jnp.zeros((B, cfg.n_heads, cfg.d_model // cfg.n_heads,
                            cfg.d_model // cfg.n_heads), jnp.float32)
            y, _, _ = ssm._rwkv6_mix(p["time_mix"], x, x_prev, cfg, s0)
            return y, mix_state

    raise ValueError(cfg.attn_type)


def layer_forward(cfg: ArchConfig, p, x, positions, kind: str,
                  is_global=None, mix_state=None, decode: bool = False,
                  pos=None, rows_only: bool = False):
    """One decoder layer. Returns (x, new_mix_state); with ``rows_only``
    (GQA and MLA decode) the new cache rows take the new state's place."""
    with jax.named_scope("pre_norm"):
        h = apply_norm(p["norm1"], x, cfg)
    y, new_state = _seq_mix(cfg, p, h, positions, is_global, mix_state,
                            decode, pos, rows_only)
    x = x + y
    with jax.named_scope("post_norm"):
        h = apply_norm(p["norm2"], x, cfg)
    if cfg.attn_type == "rwkv6":
        with jax.named_scope("channel_mix"):
            if decode:
                y2, cm_last = ssm.rwkv6_channel_mix(
                    p["channel_mix"], h, new_state["cm_shift"], cfg)
                new_state = dict(new_state, cm_shift=cm_last)
            else:
                x_prev = jnp.zeros((h.shape[0], 1, h.shape[-1]), h.dtype)
                y2, _ = ssm.rwkv6_channel_mix(p["channel_mix"], h, x_prev, cfg)
    elif "moe" in p:
        with jax.named_scope("moe"):
            y2 = moe_mod.moe_forward(p["moe"], h, cfg)
    else:
        y2 = mlp_forward(p["mlp"], h, cfg)
    return x + y2, new_state


# ---------------------------------------------------------------------------
# full model
# ---------------------------------------------------------------------------

def _n_lead(cfg: ArchConfig) -> int:
    return cfg.moe.first_k_dense if cfg.moe else 0


def _stack_kind(cfg: ArchConfig) -> str:
    return "moe" if cfg.moe else "dense"


def model_param_defs(cfg: ArchConfig) -> dict:
    d = cfg.d_model
    n_lead = _n_lead(cfg)
    n_stack = cfg.n_layers - n_lead

    def stacked(defs):  # prepend the layer-stack dim to every ParamDef
        return jax.tree_util.tree_map(
            lambda pd: ParamDef((n_stack,) + pd.shape, ("layers",) + pd.axes,
                                pd.init, pd.scale),
            defs, is_leaf=lambda v: isinstance(v, ParamDef))

    defs: Dict[str, Any] = {
        "embed": ParamDef((cfg.vocab, d), ("vocab", "embed"), scale=0.02),
        "final_norm": norm_defs(cfg),
        "layers": stacked(layer_param_defs(cfg, _stack_kind(cfg))),
    }
    if n_lead:
        defs["lead_layers"] = [layer_param_defs(cfg, "dense_lead")
                               for _ in range(n_lead)]
    if not cfg.tie_embeddings:
        defs["lm_head"] = ParamDef((d, cfg.vocab), ("embed", "vocab"),
                                   scale=0.02)
    return defs


def segments(cfg: ArchConfig):
    """Execution plan over the scanned stack: homogeneous ("scan", lo, hi)
    runs + unrolled ("global", idx) layers (hymba's 3 full-attention
    layers). Keeps per-segment sliding windows static so the flash path can
    skip out-of-window KV blocks."""
    n_stack = cfg.n_layers - _n_lead(cfg)
    globals_ = sorted(i - _n_lead(cfg) for i in cfg.global_layers
                      if i >= _n_lead(cfg))
    if cfg.sliding_window is None or not globals_:
        return [("scan", 0, n_stack)]
    segs = []
    prev = 0
    for g in globals_:
        if g > prev:
            segs.append(("scan", prev, g))
        segs.append(("global", g, g + 1))
        prev = g + 1
    if prev < n_stack:
        segs.append(("scan", prev, n_stack))
    return segs


def _tree_slice(tree, lo, hi):
    return jax.tree_util.tree_map(lambda t: t[lo:hi], tree)


def _tree_index(tree, i):
    return jax.tree_util.tree_map(lambda t: t[i], tree)


def _embed_inputs(params, batch, cfg: ArchConfig):
    """tokens -> embeddings, or pass through stub-frontend embeddings."""
    if cfg.input_mode == "embeds":
        x = batch["embeds"].astype(jnp.dtype(cfg.dtype))
    else:
        with jax.named_scope("embed"):
            x = params["embed"].astype(jnp.dtype(cfg.dtype))[batch["tokens"]]
    return constrain(x, "batch", "seq", "embed")


def _positions(batch, cfg: ArchConfig, S: int, B: int):
    if cfg.rope_type == "mrope":
        if "positions" in batch:
            return batch["positions"]
        p = jnp.arange(S, dtype=jnp.int32)[None]
        return jnp.broadcast_to(p, (3, B, S))
    return jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))


def forward(params, batch, cfg: ArchConfig, last_only: bool = False):
    """Full forward to logits. batch: tokens/embeds (+labels elsewhere).
    ``last_only`` computes the LM head for the final position only (prefill
    fast path: avoids materializing (B, S, vocab) logits)."""
    x = _embed_inputs(params, batch, cfg)
    B, S = x.shape[:2]
    positions = _positions(batch, cfg, S, B)

    for i in range(_n_lead(cfg)):
        with jax.named_scope(f"lead_layer{i}"):
            x, _ = layer_forward(cfg, params["lead_layers"][i], x, positions,
                                 "dense_lead", is_global=None)

    stack = params["layers"]
    n_stack = cfg.n_layers - _n_lead(cfg)

    def body(x, p_l):
        with jax.named_scope("layer"):
            x, _ = layer_forward(cfg, p_l, x, positions, _stack_kind(cfg),
                                 is_global=False)
        return x, None

    body_fn = jax.checkpoint(body, prevent_cse=False) if cfg.remat else body
    def global_layer(x, p_l):
        with jax.named_scope("global_layer"):
            y, _ = layer_forward(cfg, p_l, x, positions, _stack_kind(cfg),
                                 is_global=True)
        return y

    if cfg.remat:
        global_layer = jax.checkpoint(global_layer, prevent_cse=False)

    if cfg.scan_layers:
        for kind, lo, hi in segments(cfg):
            if kind == "scan":
                x, _ = lax.scan(body_fn, x, _tree_slice(stack, lo, hi))
            else:
                x = global_layer(x, _tree_index(stack, lo))
    else:
        globals_set = {i - _n_lead(cfg) for i in cfg.global_layers}
        for i in range(n_stack):
            with jax.named_scope(f"layer{i}"):
                x, _ = layer_forward(cfg, _tree_index(stack, i), x, positions,
                                     _stack_kind(cfg),
                                     is_global=i in globals_set)

    if last_only:
        x = x[:, -1:]
    with jax.named_scope("final_norm"):
        x = apply_norm(params["final_norm"], x, cfg)
    with jax.named_scope("logits"):
        head = (params["embed"].T if cfg.tie_embeddings
                else params["lm_head"])
        logits = x.astype(jnp.float32) @ head.astype(jnp.float32)
        logits = constrain(logits, "batch", "seq", "vocab")
    return logits


def loss_fn(params, batch, cfg: ArchConfig):
    """Mean token cross-entropy (f32)."""
    logits = forward(params, batch, cfg)
    labels = batch["labels"]
    with jax.named_scope("loss"):
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, labels[..., None].astype(jnp.int32),
                                   axis=-1)[..., 0]
        nll = logz - gold
        mask = batch.get("mask")
        if mask is not None:
            nll = nll * mask
            return jnp.sum(nll) / jnp.maximum(jnp.sum(mask), 1.0)
        return jnp.mean(nll)


# ---------------------------------------------------------------------------
# caches + decode
# ---------------------------------------------------------------------------

def init_layer_cache(cfg: ArchConfig, batch: int, seq_len: int, dtype,
                     window=None):
    if cfg.attn_type == "gqa":
        return attention.gqa_init_cache(cfg, batch, seq_len, dtype,
                                        window=window)
    if cfg.attn_type == "mla":
        return attention.mla_init_cache(cfg, batch, seq_len, dtype)
    if cfg.attn_type == "hymba":
        return {"kv": attention.gqa_init_cache(cfg, batch, seq_len, dtype,
                                               window=window),
                "mamba": ssm.mamba_init_cache(cfg, batch, dtype)}
    if cfg.attn_type == "rwkv6":
        return ssm.rwkv6_init_state(cfg, batch, dtype)
    raise ValueError(cfg.attn_type)


def _stack_caches(one, n):
    """``n`` copies of one layer's cache, stacked in one call, so that the
    stack is the only buffer made (an eager broadcast and copy would hold
    the stack twice)."""
    return jax.jit(lambda one: jax.tree_util.tree_map(
        lambda t: jnp.broadcast_to(t[None], (n,) + t.shape), one))(one)


def init_cache(cfg: ArchConfig, batch: int, seq_len: int):
    """Stacked (L, ...) cache pytree (+ per-lead-layer caches).

    Sliding-window layers get RING caches sized min(seq_len, window) — the
    long-context memory win (a 500k-token danube decode cache shrinks
    window/seq = 128x). Global-attention layers (hymba) keep full-length
    caches in a separate ``global`` list aligned with the execution
    segments.

    ``pos`` is a (batch,) per-slot cursor so a continuous-batching server
    can prefill one slot while others decode; aligned decode simply keeps
    all lanes equal."""
    dtype = jnp.dtype(cfg.dtype)
    n_lead = _n_lead(cfg)
    n_stack = cfg.n_layers - n_lead
    win = cfg.sliding_window
    segs = segments(cfg)
    n_globals = sum(1 for k, _, _ in segs if k == "global")
    cache = {"pos": jnp.zeros((batch,), jnp.int32)}
    if n_globals:
        ring_one = init_layer_cache(cfg, batch, seq_len, dtype, window=win)
        cache["layers"] = _stack_caches(ring_one, n_stack - n_globals)
        cache["global"] = [init_layer_cache(cfg, batch, seq_len, dtype)
                           for _ in range(n_globals)]
    else:
        one = init_layer_cache(cfg, batch, seq_len, dtype, window=win)
        cache["layers"] = _stack_caches(one, n_stack)
    if n_lead:
        cache["lead"] = [init_layer_cache(cfg, batch, seq_len, dtype)
                         for _ in range(n_lead)]
    return cache


def decode_step(params, cache, tokens, cfg: ArchConfig, embeds=None):
    """One decode step. tokens: (B,) int32 (or embeds (B,1,d) for stub
    frontends). Returns (logits (B, vocab), new cache)."""
    dtype = jnp.dtype(cfg.dtype)
    pos = cache["pos"]
    if cfg.input_mode == "embeds" and embeds is not None:
        x = embeds.astype(dtype)
    else:
        with jax.named_scope("embed"):
            x = params["embed"].astype(dtype)[tokens][:, None]
    x = constrain(x, "batch", "seq", "embed")
    B = x.shape[0]
    positions = None  # decode paths derive positions from pos

    new_cache: Dict[str, Any] = {"pos": pos + 1}
    if _n_lead(cfg):
        new_lead = []
        for i in range(_n_lead(cfg)):
            with jax.named_scope(f"lead_layer{i}"):
                x, st = layer_forward(cfg, params["lead_layers"][i], x,
                                      positions, "dense_lead", is_global=None,
                                      mix_state=cache["lead"][i], decode=True,
                                      pos=pos)
            new_lead.append(st)
        new_cache["lead"] = new_lead

    n_stack = cfg.n_layers - _n_lead(cfg)

    def make_body(is_global, rows_only=False):
        def body(x, xs):
            p_l, cache_l = xs
            with jax.named_scope("layer"):
                x, st = layer_forward(cfg, p_l, x, positions,
                                      _stack_kind(cfg), is_global=is_global,
                                      mix_state=cache_l, decode=True, pos=pos,
                                      rows_only=rows_only)
            return x, st
        return body

    if cfg.scan_layers:
        # a GQA or MLA stack reads its cache inside the scan and writes
        # every layer's new rows after it, in place when the cache is
        # donated, instead of a rewrite of each layer's whole cache
        rows_only = cfg.attn_type in ("gqa", "mla")
        scan_outs = []
        new_globals = []
        c_off = 0          # cursor into the compacted ring-cache stack
        for kind, lo, hi in segments(cfg):
            p_seg = _tree_slice(params["layers"], lo, hi)
            if kind == "scan":
                n_seg = hi - lo
                c_seg = _tree_slice(cache["layers"], c_off, c_off + n_seg)
                x, st = lax.scan(make_body(False, rows_only), x,
                                 (p_seg, c_seg))
                scan_outs.append(st)
                c_off += n_seg
            else:
                c_l = cache["global"][len(new_globals)]
                x, st1 = make_body(True)(x, (_tree_index(params["layers"], lo),
                                             c_l))
                new_globals.append(st1)
        # the scan segments cover the compacted stack in order
        new_stack = (jax.tree_util.tree_map(
            lambda *ts: jnp.concatenate(ts, axis=0), *scan_outs)
            if len(scan_outs) > 1 else scan_outs[0])
        if cfg.attn_type == "gqa":
            new_stack = attention.gqa_write(cache["layers"], new_stack, pos,
                                            window=cfg.sliding_window)
        elif cfg.attn_type == "mla":
            new_stack = attention.mla_write(cache["layers"], new_stack, pos)
        if new_globals:
            new_cache["global"] = new_globals
    else:
        globals_set = {i - _n_lead(cfg) for i in cfg.global_layers}
        outs = []
        new_globals = []
        c_off = 0
        for i in range(n_stack):
            p_l = _tree_index(params["layers"], i)
            if i in globals_set and "global" in cache:
                c_l = cache["global"][len(new_globals)]
                x, st = make_body(True)(x, (p_l, c_l))
                new_globals.append(st)
                continue
            c_l = _tree_index(cache["layers"], c_off)
            x, st = make_body(i in globals_set)(x, (p_l, c_l))
            outs.append(st)
            c_off += 1
        new_stack = jax.tree_util.tree_map(lambda *ts: jnp.stack(ts), *outs)
        if new_globals:
            new_cache["global"] = new_globals
    new_cache["layers"] = new_stack

    with jax.named_scope("final_norm"):
        x = apply_norm(params["final_norm"], x, cfg)
    with jax.named_scope("logits"):
        head = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
        logits = x[:, 0].astype(jnp.float32) @ head.astype(jnp.float32)
        logits = constrain(logits, "batch", "vocab")
    return logits, new_cache


def prefill(params, batch, cfg: ArchConfig):
    """Inference forward over a full prompt; returns last-token logits.
    (Cache population for subsequent decode reuses the training path's
    compute shape — the dry-run prefill cell measures this forward.)"""
    logits = forward(params, batch, cfg, last_only=True)
    return logits[:, 0]
