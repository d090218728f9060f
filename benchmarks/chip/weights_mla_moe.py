"""Seeded random weights of the MLA + MoE decoder (DeepSeek-V2-Lite), made
on the device in one jitted call (compiled for v5e, it holds 2.3 MB
besides the weights: each leaf is drawn straight into its buffer).

The tree is the one the program's MLA/MoE decoder takes: the leading dense
layers as a list (``lead_layers``), the MoE layers stacked (``layers``),
attention ``q_proj`` (no q LoRA), ``kv_down`` (latent then rope columns),
``kv_norm``, ``kv_up`` (per head: nope then v columns) and ``wo``; SwiGLU
``wi`` holding gate then up; the experts this chip holds stacked in
``moe/wi`` and ``moe/wo``; the router over every expert; untied head. The
reference reads the same tree. Matrices are normal with std 0.02 (output
projections 0.02 / sqrt(2 * layers)), norm scales 1 + 0.05 * normal.
Nothing here comes from the program.

The router is drawn so that a token's router logits spread by
``ROUTER_SPREAD`` (std 2 / sqrt(hidden)), as a trained router's are
sharper than a random one's: a token's top 6 of 64 gates then carry about
two thirds of the softmax and the first about 30%. Drawn as the other
matrices (a spread of 0.9 at hidden 2048), the top 6 would carry a third,
and at a rehearsal's small hidden size the routing would be nearly flat.
This is an assumption, not a reading of the published checkpoint.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmarks.chip import flops_mla_moe
from benchmarks.chip.weights import key_from_seed

# the spread (std) of a token's router logits over the experts, at any width
ROUTER_SPREAD = 2.0


def shapes(cfg: dict) -> dict:
    """``{path: (shape, std or None for a norm scale)}`` of every leaf."""
    m = flops_mla_moe.dims(cfg)
    d, h, L = m["d"], m["h"], m["layers"]
    out_std = 0.02 / math.sqrt(2 * L)

    def attn(prefix, lead):
        n = () if lead else (m["moe_layers"],)
        return {
            prefix + "norm1/scale": (n + (d,), None),
            prefix + "norm2/scale": (n + (d,), None),
            prefix + "attn/q_proj": (n + (d, h * m["qk"]), 0.02),
            prefix + "attn/kv_down": (n + (d, m["kv_lora"] + m["rope"]),
                                      0.02),
            prefix + "attn/kv_norm": (n + (m["kv_lora"],), None),
            prefix + "attn/kv_up": (n + (m["kv_lora"],
                                         h * (m["nope"] + m["v"])), 0.02),
            prefix + "attn/wo": (n + (h * m["v"], d), out_std),
        }

    out = {"embed": ((m["vocab"], d), 0.02),
           "final_norm/scale": ((d,), None),
           "lm_head": ((d, m["vocab"]), 0.02)}
    for i in range(m["dense_layers"]):
        pre = f"lead_layers/{i}/"
        out.update(attn(pre, True))
        out[pre + "mlp/wi"] = ((d, 2 * m["ff_dense"]), 0.02)
        out[pre + "mlp/wo"] = ((m["ff_dense"], d), out_std)
    n, e, f = m["moe_layers"], m["held"], m["ff_expert"]
    out.update(attn("layers/", False))
    out.update({
        "layers/moe/router": ((n, d, m["experts"]),
                              ROUTER_SPREAD / math.sqrt(d)),
        "layers/moe/wi": ((n, e, d, 2 * f), 0.02),
        "layers/moe/wo": ((n, e, f, d), out_std),
        "layers/moe/shared_wi": ((n, d, 2 * m["shared"] * f), 0.02),
        "layers/moe/shared_wo": ((n, m["shared"] * f, d), out_std),
    })
    return out


def _nest(flat: dict) -> dict:
    """Paths to a tree; a level whose keys are all digits is a list."""
    tree: dict = {}
    for path, leaf in flat.items():
        node = tree
        *head, last = path.split("/")
        for p in head:
            node = node.setdefault(p, {})
        node[last] = leaf

    def lists(node):
        if not isinstance(node, dict):
            return node
        if node and all(k.isdigit() for k in node):
            return [lists(node[k]) for k in sorted(node, key=int)]
        return {k: lists(v) for k, v in node.items()}
    return lists(tree)


def make(cfg: dict, seed: int) -> dict:
    """The weights for ``seed``, in the configuration's dtype, on the
    default device."""
    spec = shapes(cfg)
    dtype = jnp.dtype(cfg["torch_dtype"])

    @jax.jit
    def build(key):
        keys = jax.random.split(key, len(spec))
        flat = {}
        for k, (path, (shape, std)) in zip(keys, sorted(spec.items())):
            # drawn in the served dtype: no float32 copy of a leaf is held
            z = jax.random.normal(k, shape, dtype)
            flat[path] = (1.0 + 0.05 * z if std is None else std * z
                          ).astype(dtype)
        return flat

    return _nest(build(key_from_seed(seed)))
