"""Shared model building blocks: param definitions, norms, RoPE family."""
from __future__ import annotations

import dataclasses
import math
from typing import Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.distributed.sharding import constrain


# --------------------------------------------------------------------------
# parameter definitions: one source of truth for shape + logical axes + init
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ParamDef:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]   # logical axis names, len == rank
    init: str = "normal"              # normal | zeros | ones | small_normal
    scale: float = 0.02

    def initializer(self, key, dtype):
        if self.init == "zeros":
            return jnp.zeros(self.shape, dtype)
        if self.init == "ones":
            return jnp.ones(self.shape, dtype)
        std = self.scale
        return (jax.random.normal(key, self.shape, jnp.float32) * std).astype(dtype)


def init_tree(defs, key, dtype):
    """Initialize a pytree of ParamDef into arrays (deterministic per path)."""
    leaves, treedef = jax.tree_util.tree_flatten(
        defs, is_leaf=lambda x: isinstance(x, ParamDef))
    keys = jax.random.split(key, len(leaves))
    arrs = [d.initializer(k, dtype) for d, k in zip(leaves, keys)]
    return jax.tree_util.tree_unflatten(treedef, arrs)


def abstract_tree(defs, dtype):
    """ShapeDtypeStruct pytree (no allocation) for dry-runs."""
    return jax.tree_util.tree_map(
        lambda d: jax.ShapeDtypeStruct(d.shape, dtype),
        defs, is_leaf=lambda x: isinstance(x, ParamDef))


def axes_tree(defs):
    return jax.tree_util.tree_map(
        lambda d: d.axes, defs, is_leaf=lambda x: isinstance(x, ParamDef))


def count_params(defs) -> int:
    leaves = jax.tree_util.tree_leaves(
        defs, is_leaf=lambda x: isinstance(x, ParamDef))
    return sum(math.prod(d.shape) for d in leaves)


# --------------------------------------------------------------------------
# norms (f32 internal math regardless of activation dtype)
# --------------------------------------------------------------------------

def rmsnorm(x, scale, eps: float = 1e-5):
    with jax.named_scope("rmsnorm"):
        xf = x.astype(jnp.float32)
        var = jnp.mean(xf * xf, axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(var + eps)
        return (y * scale.astype(jnp.float32)).astype(x.dtype)


def layernorm(x, scale, bias, eps: float = 1e-5):
    with jax.named_scope("layernorm"):
        xf = x.astype(jnp.float32)
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps)
        return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(x.dtype)


# --------------------------------------------------------------------------
# activations
# --------------------------------------------------------------------------

def swiglu(gate_up):
    gate, up = jnp.split(gate_up, 2, axis=-1)
    return jax.nn.silu(gate) * up


ACTIVATIONS = {
    "swiglu": swiglu,                    # expects fused (…, 2*d_ff)
    "gelu": jax.nn.gelu,
    "relu": jax.nn.relu,
}


# --------------------------------------------------------------------------
# RoPE family: standard, partial, and M-RoPE (Qwen2-VL)
# --------------------------------------------------------------------------

def rope_freqs(rotary_dim: int, theta: float, scaling=None) -> jnp.ndarray:
    """Inverse frequencies of the rotary pairs; with ``scaling`` (a
    :class:`~repro.configs.base.YarnScaling`) DeepSeek's YaRN blend of the
    original and the interpolated frequencies."""
    base = 1.0 / (theta ** (jnp.arange(0, rotary_dim, 2, dtype=jnp.float32)
                            / rotary_dim))
    if scaling is None:
        return base
    low, high = yarn_correction_range(scaling, rotary_dim, theta)
    ramp = jnp.clip((jnp.arange(rotary_dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    # ramp 0 keeps a pair's original frequency, 1 divides it by the factor
    return base / scaling.factor * ramp + base * (1.0 - ramp)


def yarn_correction_range(scaling, rotary_dim: int, theta: float):
    """The pairs over which YaRN's blend ramps: from the one that turns
    ``beta_fast`` times over the original context to the one that turns
    ``beta_slow`` times, clamped to the pairs there are."""
    def dim(rotations):
        return (rotary_dim * math.log(scaling.original_max_position_embeddings
                                      / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))
    return (max(math.floor(dim(scaling.beta_fast)), 0),
            min(math.ceil(dim(scaling.beta_slow)), rotary_dim - 1))


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rope_scale(scaling) -> float:
    """YaRN's factor on cos and sin (1 where mscale == mscale_all_dim)."""
    if scaling is None:
        return 1.0
    return (yarn_mscale(scaling.factor, scaling.mscale)
            / yarn_mscale(scaling.factor, scaling.mscale_all_dim))


def _rotate(x, sin, cos):
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def apply_rope(x, positions, *, theta: float = 1e4, fraction: float = 1.0,
               scaling=None):
    """x: (B, H, S, D); positions: (B, S) int. Rotary applied to the first
    ``fraction`` of D (GLM-4 uses 0.5); ``scaling``: YaRN (MLA)."""
    d = x.shape[-1]
    rd = int(d * fraction)
    rd -= rd % 2
    if rd == 0:
        return x
    inv = rope_freqs(rd, theta, scaling)                         # (rd/2,)
    ang = positions.astype(jnp.float32)[:, None, :, None] * inv  # (B,1,S,rd/2)
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    m = rope_scale(scaling)
    if m != 1.0:
        sin, cos = sin * m, cos * m
    xr, xp = x[..., :rd], x[..., rd:]
    xr = _rotate(xr.astype(jnp.float32), sin, cos).astype(x.dtype)
    return jnp.concatenate([xr, xp], axis=-1) if rd < d else xr


def apply_mrope(x, positions, *, theta: float, sections: Sequence[int]):
    """Multimodal RoPE (Qwen2-VL): ``positions`` is (3, B, S) for the
    temporal/height/width indices; ``sections`` split the rd/2 frequency
    channels among the three position streams."""
    d = x.shape[-1]
    rd = 2 * sum(sections)
    assert rd <= d, (rd, d)
    inv = rope_freqs(rd, theta)                                   # (rd/2,)
    ang_tHW = positions.astype(jnp.float32)[:, :, None, :, None] * inv
    # select per-channel which stream drives the angle
    stream = jnp.repeat(jnp.arange(3), jnp.asarray(sections),
                        total_repeat_length=rd // 2)              # (rd/2,)
    ang = jnp.take_along_axis(
        ang_tHW, stream[None, None, None, None, :].astype(jnp.int32),
        axis=0)[0]                                                # (B,1,S,rd/2)
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    xr, xp = x[..., :rd], x[..., rd:]
    xr = _rotate(xr.astype(jnp.float32), sin, cos).astype(x.dtype)
    return jnp.concatenate([xr, xp], axis=-1) if rd < d else xr
