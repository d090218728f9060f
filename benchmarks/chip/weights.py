"""Seeded random weights, made on the device in one jitted call.

The tree is the one the program's dense decoder takes (stacked layers,
SwiGLU ``wi`` holding gate then up, untied head); the reference reads the
same tree. Matrices are normal with std 0.02 (the output projections
0.02 / sqrt(2 * layers)), norm scales 1 + 0.05 * normal, so that a norm
that ignored its scale would show. Nothing here comes from the program.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmarks.chip import flops


def key_from_seed(seed: int):
    """A PRNG key from any non-negative seed, 64-bit ones included."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def shapes(cfg: dict) -> dict:
    """``{path: (shape, std or None for a norm scale)}`` of every leaf."""
    m = flops.dims(cfg)
    d, L, hd = m["d"], m["layers"], m["hd"]
    out_std = 0.02 / math.sqrt(2 * L)
    return {
        "embed": ((m["vocab"], d), 0.02),
        "final_norm/scale": ((d,), None),
        "lm_head": ((d, m["vocab"]), 0.02),
        "layers/norm1/scale": ((L, d), None),
        "layers/norm2/scale": ((L, d), None),
        "layers/attn/wq": ((L, d, m["h"] * hd), 0.02),
        "layers/attn/wk": ((L, d, m["kv"] * hd), 0.02),
        "layers/attn/wv": ((L, d, m["kv"] * hd), 0.02),
        "layers/attn/wo": ((L, m["h"] * hd, d), out_std),
        "layers/mlp/wi": ((L, d, 2 * m["ff"]), 0.02),
        "layers/mlp/wo": ((L, m["ff"], d), out_std),
    }


def _nest(flat: dict) -> dict:
    tree: dict = {}
    for path, leaf in flat.items():
        node = tree
        *head, last = path.split("/")
        for p in head:
            node = node.setdefault(p, {})
        node[last] = leaf
    return tree


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
        return _nest(flat)

    return build(key_from_seed(seed))
