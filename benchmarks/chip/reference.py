"""Plain reference of the benchmark's dense decoders, and the comparison
that decides ``correct``.

A straightforward causal forward pass of a llama-style decoder (RMSNorm,
GQA attention with rotate-half RoPE and an optional sliding window, SwiGLU
feed-forward, untied head) in float32 with every matrix product at
``Precision.HIGHEST``: on a TPU a float32 product otherwise runs one bf16
pass. It imports nothing of the program. The truncation policy under test
is applied as its definition says: the result of each floating-point
operation in the policy's scope is rounded to the policy's (e, m) grid,
round-to-nearest-even, with subnormals and overflow to infinity.

``lowp=True`` is the control: the same pass computed in fp8 (e4m3,
saturating at 448), with every operation's result rounded to e4m3 and
every weight matrix stored in e4m3 with a per-tensor scale. It is the step
below the configuration's bfloat16; put in the program's place, it must
fail the comparison.
"""
from __future__ import annotations

import functools
import re
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmarks.chip import flops

_HI = lax.Precision.HIGHEST
_E4M3_MAX = 448.0


def round_em(x, exp_bits: int, man_bits: int):
    """Round float32 ``x`` to the IEEE-style (exp_bits, man_bits) grid:
    nearest, ties to even, gradual underflow, overflow to +-inf."""
    bias = 2 ** (exp_bits - 1) - 1
    emin, emax = 1 - bias, bias
    _, k = jnp.frexp(x)
    e = jnp.maximum(k - 1, emin)
    q = jnp.ldexp(jnp.round(jnp.ldexp(x, man_bits - e)), e - man_bits)
    q = jnp.where(jnp.abs(q) > (2.0 - 2.0 ** -man_bits) * 2.0 ** emax,
                  jnp.copysign(jnp.inf, x), q)
    return jnp.where(jnp.isfinite(x), q, x)


def round_e4m3(x):
    return jnp.clip(x, -_E4M3_MAX, _E4M3_MAX).astype(
        jnp.float8_e4m3fn).astype(jnp.float32)


def weight_e4m3(w):
    scale = jnp.maximum(jnp.max(jnp.abs(w)), 1e-30) / _E4M3_MAX
    return round_e4m3(w / scale) * scale


_POLICY = re.compile(r"^scope:\*\*(?:/(\w+))?=e(\d+)m(\d+)$")


def parse_policy(spec: Optional[str]) -> Optional[Tuple[str, int, int]]:
    """``scope:**/mlp=e5m7`` -> ("mlp", 5, 7); ``scope:**=e8m4`` ->
    ("", 8, 4); None -> None."""
    if spec is None:
        return None
    m = _POLICY.match(spec)
    if not m:
        raise ValueError(f"reference cannot read policy {spec!r}")
    return m.group(1) or "", int(m.group(2)), int(m.group(3))


def _mm(a, b):
    return jnp.matmul(a, b, precision=_HI)


def _rmsnorm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


def _rope(x, theta):
    """x: (T, heads, hd); positions 0..T-1; rotate-half convention."""
    t, _, hd = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None, None] * inv
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def forward(weights: dict, tokens, cfg: dict, policy: Optional[str],
            lowp: bool = False):
    """Logits (T, vocab) in float32 for one sequence of ``tokens`` (T,)."""
    m = flops.dims(cfg)
    eps = cfg["rms_norm_eps"]
    theta = cfg["rope_theta"]
    pol = parse_policy(policy)
    f32 = jnp.float32
    act: Callable = round_e4m3 if lowp else (lambda v: v)
    wt: Callable = weight_e4m3 if lowp else (lambda v: v.astype(f32))

    def rounding(scopes) -> Callable:
        """Operations outside the policy's scope keep their precision."""
        if pol is None or pol[0] not in scopes:
            return act
        return lambda v: round_em(act(v), pol[1], pol[2])

    everywhere = rounding(("",))
    in_layer = rounding(("", "layer"))
    mlp = rounding(("", "layer", "mlp"))
    t = tokens.shape[0]
    h, kv, hd, ff = m["h"], m["kv"], m["hd"], m["ff"]
    q_pos = jnp.arange(t)[:, None]
    k_pos = jnp.arange(t)[None, :]
    mask = k_pos <= q_pos
    if m["window"]:
        mask &= (q_pos - k_pos) < m["window"]

    x = everywhere(wt(weights["embed"])[tokens])

    def layer(x, p):
        r = in_layer
        hn = r(_rmsnorm(x, wt(p["norm1"]["scale"]), eps))
        q = r(_mm(hn, wt(p["attn"]["wq"]))).reshape(t, h, hd)
        k = r(_mm(hn, wt(p["attn"]["wk"]))).reshape(t, kv, hd)
        v = r(_mm(hn, wt(p["attn"]["wv"]))).reshape(t, kv, hd)
        q, k = r(_rope(q, theta)), r(_rope(k, theta))
        qg = q.reshape(t, kv, h // kv, hd)
        s = r(jnp.einsum("qkgd,tkd->kgqt", qg, k, precision=_HI)
              / jnp.sqrt(f32(hd)))
        s = jnp.where(mask[None, None], s, -jnp.inf)
        pr = r(jax.nn.softmax(s, axis=-1))
        o = r(jnp.einsum("kgqt,tkd->qkgd", pr, v, precision=_HI))
        x = r(x + r(_mm(o.reshape(t, h * hd), wt(p["attn"]["wo"]))))
        hn = r(_rmsnorm(x, wt(p["norm2"]["scale"]), eps))
        a = mlp(_mm(hn, wt(p["mlp"]["wi"])))
        gate, up = a[:, :ff], a[:, ff:]
        g = mlp(gate * mlp(jax.nn.sigmoid(gate)))
        y = mlp(_mm(mlp(g * up), wt(p["mlp"]["wo"])))
        return r(x + y), None

    x, _ = lax.scan(layer, x, weights["layers"])
    x = everywhere(_rmsnorm(x, wt(weights["final_norm"]["scale"]), eps))
    return everywhere(_mm(x, wt(weights["lm_head"])))


def mlp_elements_per_token(cfg: dict) -> int:
    """Elements that the policy rounds in one layer's MLP for one token, as
    ``forward`` rounds them: the fused gate-and-up product (2F), the
    sigmoid, the SiLU product and the gating product (F each), and the down
    product (d). Taking the two halves of the first product moves data and
    computes nothing, so nothing is rounded there."""
    m = flops.dims(cfg)
    return 5 * m["ff"] + m["d"]


def cfg_items(cfg: dict) -> tuple:
    """The configuration's numbers and names as a hashable static
    argument of a jitted pass."""
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, str, type(None)))))


@functools.partial(jax.jit, static_argnames=("cfg_items", "policy", "lowp"))
def nll_sum(weights, tokens, labels, cfg_items, policy, lowp=False):
    """Summed token cross-entropy of one sequence."""
    logits = forward(weights, tokens, dict(cfg_items), policy, lowp=lowp)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.sum(logz - gold)


@functools.partial(jax.jit, static_argnames=("cfg_items", "policy", "lowp"))
def _gaps(weights, tokens, served, cfg_items, policy, lowp):
    cfg = dict(cfg_items)
    ref = forward(weights, tokens, cfg, policy)
    if lowp:
        # the control in the program's place: its tokens are those the fp8
        # pass puts first at each position
        served = jnp.argmax(forward(weights, tokens, cfg, policy, lowp=True),
                            axis=-1)
    got = jnp.take_along_axis(ref, served[:, None], axis=-1)[:, 0]
    return jnp.max(ref, axis=-1) - got


def served_gaps(weights, cfg: dict, policy: Optional[str], prompt, out,
                length: int, control: bool = False):
    """Gaps by which each served token's reference logit lies below the
    reference's best, for one request (``prompt`` then ``out`` tokens),
    padded to ``length`` so that one compiled pass serves every request.
    With ``control``, the gaps of the tokens that the fp8 pass puts first
    at the same positions, in place of the served ones."""
    seq = np.concatenate([prompt, out[:-1]]).astype(np.int32)
    n = len(seq)
    toks = np.zeros(length, np.int32)
    toks[:n] = seq
    served = np.zeros(length, np.int32)
    first = len(prompt) - 1
    served[first:first + len(out)] = out
    res = _gaps(weights, jnp.asarray(toks), jnp.asarray(served),
                cfg_items(cfg), policy, control)
    return np.asarray(res)[first:first + len(out)]
