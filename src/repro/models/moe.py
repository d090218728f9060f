"""Mixture-of-Experts: an expert-share layer, dropless.

The router keeps its published width: it scores every expert, in float32
(softmax, top-k, optional renormalisation, ``routed_scaling_factor``).
The layer holds the experts that ``MoEConfig.held`` names, the share of
one chip under expert parallelism (all of them by default), and computes
their part of the result for the tokens routed to them; what the absent
experts add is left out, and that partial result goes on to the next
layer. Shared experts are added once. No token is dropped.

Two ways to compute the held experts' part, chosen by the token count,
which is static:

* up to :data:`DENSE_TOKENS` tokens (a decode tick), every held expert runs
  over every token and unrouted tokens get a gate of zero: the layer is
  bound by reading the held weights, which either way are read once;
* above it (training, prefill, profiling), the (token, expert) pairs are
  sorted by expert and run as one grouped matrix product
  (``lax.ragged_dot``) over the pairs routed to held experts: the routed
  operations and nothing else on TPU, where the grouped product skips the
  rows past its groups.

Each expert's feed-forward is under the ``mlp`` scope, as a dense layer's
is, so a policy on ``**/mlp`` rounds dense, routed and shared
feed-forwards alike and leaves the router unrounded. The layer's scopes
are ``moe/router``, ``moe/experts`` and ``moe/shared``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ArchConfig, MoEConfig
from repro.models.common import ParamDef, ACTIVATIONS

# above this many tokens the held experts run as a grouped product over the
# routed pairs; at or below it (decode) densely over all tokens
DENSE_TOKENS = 128


def moe_param_defs(cfg: ArchConfig) -> dict:
    mc = cfg.moe
    d = cfg.d_model
    n_held = len(mc.held)
    o_scale = 0.02 / math.sqrt(2 * cfg.n_layers)
    defs = {
        "router": ParamDef((d, mc.n_experts), ("embed", None)),
        "wi": ParamDef((n_held, d, 2 * mc.d_expert),
                       ("experts", "embed", "mlp")),
        "wo": ParamDef((n_held, mc.d_expert, d),
                       ("experts", "mlp", "embed"), scale=o_scale),
    }
    if mc.n_shared:
        defs["shared_wi"] = ParamDef((d, 2 * mc.n_shared * mc.d_expert),
                                     ("embed", "mlp"))
        defs["shared_wo"] = ParamDef((mc.n_shared * mc.d_expert, d),
                                     ("mlp", "embed"), scale=o_scale)
    return defs


def route(router, x, mc: MoEConfig):
    """(expert ids, gates), each (T, k): softmax over every expert in
    float32, the top k, renormalised where the configuration says so, then
    scaled by ``routed_scaling_factor``."""
    logits = x.astype(jnp.float32) @ router.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, ids = lax.top_k(probs, mc.top_k)
    if mc.renormalize:
        gates = gates / jnp.maximum(jnp.sum(gates, -1, keepdims=True), 1e-9)
    return ids, gates * mc.routed_scaling_factor


def _ffn(x, wi, wo):
    """One SwiGLU feed-forward, under the ``mlp`` scope."""
    with jax.named_scope("mlp"):
        return ACTIVATIONS["swiglu"](x @ wi) @ wo


def _held_dense(p, x, ids, gates, held):
    """Every held expert over every token; a token's weight for an expert
    is its gate where it was routed there and zero elsewhere."""
    held = jnp.asarray(held, ids.dtype)
    weight = jnp.sum(jnp.where(ids[:, :, None] == held, gates[:, :, None],
                               0.0), axis=1)                  # (T, n_held)
    # the tokens broadcast over the held experts, so that each product is
    # batched over experts and reads the weights as they lie
    xe = jnp.broadcast_to(x, (held.shape[0],) + x.shape)
    with jax.named_scope("mlp"):
        h = jnp.einsum("etd,edf->etf", xe, p["wi"].astype(x.dtype))
        h = ACTIVATIONS["swiglu"](h)
        y = jnp.einsum("etf,efd->etd", h, p["wo"].astype(x.dtype))
    return jnp.einsum("te,etd->td", weight, y.astype(jnp.float32))


def _held_grouped(p, x, ids, gates, mc: MoEConfig):
    """The (token, expert) pairs routed to held experts, sorted by expert,
    through one grouped product; pairs routed elsewhere sort last and lie
    past every group, where the product gives zeros. Gathers only (no
    scatter), in both directions."""
    T, K = ids.shape
    n_held = len(mc.held)
    match = ids.reshape(-1)[:, None] == jnp.asarray(mc.held, ids.dtype)
    local = jnp.where(jnp.any(match, -1), jnp.argmax(match, -1), n_held)
    order = jnp.argsort(local, stable=True)           # pairs by held expert
    sizes = jnp.sum(match, axis=0, dtype=jnp.int32)   # (n_held,)
    xs = x[order // K]
    with jax.named_scope("mlp"):
        h = lax.ragged_dot(xs, p["wi"].astype(x.dtype), sizes)
        h = ACTIVATIONS["swiglu"](h)
        y = lax.ragged_dot(h, p["wo"].astype(x.dtype), sizes)
    # back to (token, k) order; a pair routed elsewhere reads a zero row
    y = y[jnp.argsort(order)].reshape(T, K, -1).astype(jnp.float32)
    return jnp.einsum("tk,tkd->td", gates, y)


def moe_forward(p, x, cfg: ArchConfig):
    """x: (B, S, d) -> (B, S, d): the held experts' part of the routed
    result, plus the shared experts."""
    mc = cfg.moe
    B, S, d = x.shape
    T = B * S
    xf = x.reshape(T, d)
    with jax.named_scope("router"):
        ids, gates = route(p["router"], xf, mc)
    with jax.named_scope("experts"):
        if T <= DENSE_TOKENS:
            y = _held_dense(p, xf, ids, gates, mc.held)
        else:
            y = _held_grouped(p, xf, ids, gates, mc)
        y = y.astype(x.dtype)
    if mc.n_shared:
        with jax.named_scope("shared"):
            y = y + _ffn(xf, p["shared_wi"].astype(x.dtype),
                         p["shared_wo"].astype(x.dtype))
    return y.reshape(B, S, d)

