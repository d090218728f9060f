"""Plain reference of the MLA + MoE decoder (DeepSeek-V2-Lite) as one
expert-parallel chip holds it, and the comparison that decides
``correct``.

A straightforward causal forward pass in float32 with every matrix product
at ``Precision.HIGHEST``, following DeepSeek-V2's published modelling code:
RMSNorm; multi-head latent attention in its expanded form (q one plain
projection; the latent ``kv_down`` then its norm, ``kv_up`` to each head's
key and value; YaRN RoPE on the rope half of q and of the shared rope key,
its blend of original and interpolated frequencies over the correction
range of ``beta_fast`` and ``beta_slow``, cos and sin scaled by
mscale/mscale_all_dim, the softmax scale times mscale(mscale_all_dim)^2);
the leading dense layers' SwiGLU; then the MoE layers: softmax routing in
float32 over every expert, the top k, no renormalisation where
``norm_topk_prob`` is false, times ``routed_scaling_factor``; the held
experts' part of the result (each held expert's SwiGLU for the tokens
routed to it, weighted by its gate), plus the shared experts. What the
absent experts would add is left out, as the program leaves it out.
Untied head. It imports nothing of the program.

Departure from the published code: the rope half is rotated in
rotate-half order (pairs (i, i + 32)), where DeepSeek's checkpoint keeps
the interleaved pairs (2i, 2i + 1) and permutes them before rotating. With
random weights the two are the same model up to a permutation of the
weights' rope columns.

The truncation policy is applied as ``reference.py`` applies it: the
result of each floating-point operation in the policy's scope (here the
feed-forwards, ``mlp``) is rounded to the policy's (e, m) grid.
``lowp=True`` is the control, the same pass in fp8 (e4m3), which put in
the program's place must fail the comparison.
"""
from __future__ import annotations

import functools
import math
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmarks.chip import flops_mla_moe
from benchmarks.chip.reference import (
    parse_policy, round_e4m3, round_em, weight_e4m3)

_HI = lax.Precision.HIGHEST


def _mm(a, b):
    return jnp.matmul(a, b, precision=_HI)


def _rmsnorm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn(cfg: dict):
    """(inverse frequencies (rope/2,), cos/sin factor, softmax scale), as
    ``DeepseekV2YarnRotaryEmbedding`` and ``softmax_scale`` compute them."""
    m = flops_mla_moe.dims(cfg)
    dim, base = m["rope"], float(cfg["rope_theta"])
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    scale = 1.0 / math.sqrt(m["qk"])
    rs = cfg.get("rope_scaling")
    if not rs:
        return extra.astype(np.float32), 1.0, scale
    factor = float(rs["factor"])
    inter = extra / factor

    def corr(rot):
        return (dim * math.log(rs["original_max_position_embeddings"]
                               / (rot * 2 * math.pi))) / (2 * math.log(base))
    low = max(math.floor(corr(rs["beta_fast"])), 0)
    high = min(math.ceil(corr(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    mask = 1.0 - ramp
    inv = inter * (1 - mask) + extra * mask
    cs = (_yarn_mscale(factor, rs["mscale"])
          / _yarn_mscale(factor, rs["mscale_all_dim"]))
    if rs.get("mscale_all_dim"):
        scale *= _yarn_mscale(factor, rs["mscale_all_dim"]) ** 2
    return inv.astype(np.float32), float(cs), float(scale)


def _rope(x, inv, cs):
    """x: (T, heads, dim); positions 0..T-1; rotate-half pairs."""
    t, _, dim = x.shape
    ang = jnp.arange(t, dtype=jnp.float32)[:, None, None] * jnp.asarray(inv)
    sin, cos = jnp.sin(ang) * cs, jnp.cos(ang) * cs
    x1, x2 = x[..., :dim // 2], x[..., dim // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def forward(weights: dict, tokens, cfg: dict, policy: Optional[str],
            lowp: bool = False):
    """Logits (T, vocab) in float32 for one sequence of ``tokens`` (T,)."""
    m = flops_mla_moe.dims(cfg)
    eps = cfg["rms_norm_eps"]
    inv, cs, scale = yarn(cfg)
    pol = parse_policy(policy)
    act: Callable = round_e4m3 if lowp else (lambda v: v)
    wt: Callable = weight_e4m3 if lowp else (lambda v: v.astype(jnp.float32))

    def rounding(scopes) -> Callable:
        """Operations outside the policy's scope keep their precision."""
        if pol is None or pol[0] not in scopes:
            return act
        return lambda v: round_em(act(v), pol[1], pol[2])

    r = rounding(("", "layer"))
    mlp = rounding(("", "layer", "mlp"))
    t = tokens.shape[0]
    h, nope, rope, vd, kv_lora = (m["h"], m["nope"], m["rope"], m["v"],
                                  m["kv_lora"])
    held = cfg["expert_parallel"]["held_experts"]
    mask = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]

    def attention(x, p):
        q = r(_mm(x, wt(p["q_proj"]))).reshape(t, h, nope + rope)
        kv = r(_mm(x, wt(p["kv_down"])))
        c_kv = r(_rmsnorm(kv[:, :kv_lora], wt(p["kv_norm"]), eps))
        k_pe = r(_rope(kv[:, None, kv_lora:], inv, cs))          # (T,1,dr)
        up = r(_mm(c_kv, wt(p["kv_up"]))).reshape(t, h, nope + vd)
        q_pe = r(_rope(q[..., nope:], inv, cs))
        k = jnp.concatenate(
            [up[..., :nope], jnp.broadcast_to(k_pe, (t, h, rope))], -1)
        qq = jnp.concatenate([q[..., :nope], q_pe], -1)
        s = r(jnp.einsum("qhd,khd->hqk", qq, k, precision=_HI) * scale)
        s = jnp.where(mask[None], s, -jnp.inf)
        pr = r(jax.nn.softmax(s, axis=-1))
        o = r(jnp.einsum("hqk,khd->qhd", pr, up[..., nope:], precision=_HI))
        return r(_mm(o.reshape(t, h * vd), wt(p["wo"])))

    def swiglu(x, wi, wo):
        a = mlp(_mm(x, wt(wi)))
        ff = a.shape[-1] // 2
        gate, up = a[:, :ff], a[:, ff:]
        g = mlp(gate * mlp(jax.nn.sigmoid(gate)))
        return mlp(_mm(mlp(g * up), wt(wo)))

    def moe(x, p):
        probs = jax.nn.softmax(r(_mm(x, wt(p["router"]))), axis=-1)
        gates, ids = lax.top_k(probs, m["top_k"])
        if cfg["norm_topk_prob"]:
            gates = gates / jnp.sum(gates, -1, keepdims=True)
        gates = gates * cfg["routed_scaling_factor"]
        y = jnp.zeros_like(x)
        for j, e in enumerate(held):
            g = jnp.sum(jnp.where(ids == e, gates, 0.0), axis=-1)  # (T,)
            y = y + g[:, None] * swiglu(x, p["wi"][j], p["wo"][j])
        return r(y + swiglu(x, p["shared_wi"], p["shared_wo"]))

    def block(x, p, ffn):
        x = r(x + attention(r(_rmsnorm(x, wt(p["norm1"]["scale"]), eps)),
                            p["attn"]))
        hn = r(_rmsnorm(x, wt(p["norm2"]["scale"]), eps))
        return r(x + ffn(hn))

    x = r(wt(weights["embed"])[tokens])
    for p in weights.get("lead_layers", []):
        x = block(x, p, lambda hn, p=p: swiglu(hn, p["mlp"]["wi"],
                                               p["mlp"]["wo"]))

    def layer(x, p):
        return block(x, p, lambda hn: moe(hn, p["moe"])), None

    if m["moe_layers"]:
        x, _ = lax.scan(layer, x, weights["layers"])
    x = r(_rmsnorm(x, wt(weights["final_norm"]["scale"]), eps))
    return r(_mm(x, wt(weights["lm_head"])))


@functools.partial(jax.jit, static_argnames=("cfg_items", "policy", "lowp"))
def _compare(weights, tokens, served, cfg_items, policy, lowp):
    """(reference's best logit, its logit of the served token, the fp8
    pass's best logit) at each position. With ``lowp`` the served tokens
    are those the fp8 pass puts first: the control in the program's
    place."""
    cfg = _unfreeze(cfg_items)
    ref = forward(weights, tokens, cfg, policy)
    low = jnp.zeros(tokens.shape, jnp.float32)
    if lowp:
        lp = forward(weights, tokens, cfg, policy, lowp=True)
        served, low = jnp.argmax(lp, axis=-1), jnp.max(lp, axis=-1)
    got = jnp.take_along_axis(ref, served[:, None], axis=-1)[:, 0]
    return jnp.max(ref, axis=-1), got, low


def _freeze(cfg: dict) -> tuple:
    """The configuration, nested groups included, as a hashable static
    argument of a jitted pass."""
    out = []
    for k, v in sorted(cfg.items()):
        if isinstance(v, dict):
            out.append((k, ("dict", _freeze(v))))
        elif isinstance(v, list):
            out.append((k, ("list", tuple(v))))
        elif isinstance(v, (int, float, str, type(None))):
            out.append((k, v))
    return tuple(out)


def _unfreeze(items: tuple) -> dict:
    out = {}
    for k, v in items:
        if isinstance(v, tuple) and v and v[0] == "dict":
            out[k] = _unfreeze(v[1])
        elif isinstance(v, tuple) and v and v[0] == "list":
            out[k] = list(v[1])
        else:
            out[k] = v
    return out


def _served(weights, cfg, policy, prompt, out, length, control):
    """``_compare`` over one request (``prompt`` then ``out`` tokens),
    padded to ``length`` so that one compiled pass serves every request,
    at the positions that put out each served token."""
    seq = np.concatenate([prompt, out[:-1]]).astype(np.int32)
    toks = np.zeros(length, np.int32)
    toks[:len(seq)] = seq
    served = np.zeros(length, np.int32)
    first = len(prompt) - 1
    served[first:first + len(out)] = out
    res = _compare(weights, jnp.asarray(toks), jnp.asarray(served),
                   _freeze(cfg), policy, control)
    return [np.asarray(v)[first:first + len(out)] for v in res]


def served_gaps(weights, cfg: dict, policy: Optional[str], prompt, out,
                length: int, control: bool = False):
    """Gaps by which each served token's reference logit lies below the
    reference's best, for one request. With ``control``, the gaps of the
    tokens that the fp8 pass puts first at the same positions, in place of
    the served ones."""
    best, got, _ = _served(weights, cfg, policy, prompt, out, length,
                           control)
    return best - got


def served_logit_errors(weights, cfg: dict, policy: Optional[str], prompt,
                        out, program: dict, length: int,
                        control: bool = False):
    """How far the logit the program gave each served token lies from the
    reference's logit of that token, at the served positions ``program``
    names (``{index into out: the program's logit}``). With ``control``,
    the fp8 pass's logit of the token it puts first at each of those
    positions, in place of the program's."""
    _, got, low = _served(weights, cfg, policy, prompt, out, length,
                          control)
    idx = np.array(sorted(program), np.int64)
    mine = low[idx] if control else np.array([program[k] for k in idx])
    return np.abs(mine - got[idx])
