"""Logical-axis sharding rules (FSDP x TP x EP x (pod)DP).

Models annotate activations with *logical* axis names via ``constrain``;
parameters carry logical axes in their ``ParamDef``. A ``ShardingRules``
table maps logical names onto mesh axes at lower time. When no mesh is
active (CPU smoke tests) every annotation is a no-op.

Conventions (see DESIGN.md §6):
  activations:  batch -> (pod?, data), heads/kv/mlp/experts -> model,
                embed/seq -> replicated (seq -> model for long-context KV
                caches: context parallelism)
  parameters:   embed -> data (FSDP), heads/mlp/vocab/experts -> model,
                layer stack dim -> replicated
Divisibility guard: an annotation on a dim not divisible by its mesh axis is
dropped (e.g. kv_heads=2 on a 16-way model axis falls back to replicated and
attention re-shards on q-heads instead).
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
import threading
from typing import Optional, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Axis = Union[None, str, Tuple[str, ...]]

# logical name -> mesh axis (or tuple) for ACTIVATIONS
DEFAULT_ACT_RULES = {
    "batch": ("pod", "data"),
    "seq": None,
    "cache_seq": "model",   # context-parallel KV cache for decode
    "embed": None,
    "heads": "model",
    "kv_heads": "model",
    "qk": None,
    "mlp": "model",
    "experts": "model",
    "vocab": "model",
    "state": None,
}

# logical name -> mesh axis for PARAMETERS (training: FSDP x TP)
DEFAULT_PARAM_RULES = {
    "layers": None,
    "embed": "data",        # FSDP
    "heads": "model",
    "kv_heads": "model",
    "mlp": "model",
    "experts": "model",
    "vocab": "model",
    "qk": None,
    "state": None,
    "conv": None,
}


# serving: TP-only — no per-layer FSDP all-gathers on the decode critical
# path (used when bf16 params fit a single model-parallel shard group)
SERVE_PARAM_RULES = {**DEFAULT_PARAM_RULES, "embed": None}


@dataclasses.dataclass
class ShardingContext:
    mesh: Optional[Mesh] = None
    act_rules: dict = dataclasses.field(default_factory=lambda: dict(DEFAULT_ACT_RULES))
    param_rules: dict = dataclasses.field(default_factory=lambda: dict(DEFAULT_PARAM_RULES))


_state = threading.local()


def _ctx() -> ShardingContext:
    if not hasattr(_state, "ctx"):
        _state.ctx = ShardingContext()
    return _state.ctx


@contextlib.contextmanager
def use_mesh(mesh: Optional[Mesh], act_rules: Optional[dict] = None,
             param_rules: Optional[dict] = None):
    old = getattr(_state, "ctx", None)
    _state.ctx = ShardingContext(
        mesh=mesh,
        act_rules=dict(act_rules or DEFAULT_ACT_RULES),
        param_rules=dict(param_rules or DEFAULT_PARAM_RULES),
    )
    try:
        if mesh is not None:
            with mesh:
                yield _state.ctx
        else:
            yield _state.ctx
    finally:
        if old is None:
            del _state.ctx
        else:
            _state.ctx = old


def _mesh_axis_size(mesh: Mesh, axis: Axis) -> int:
    if axis is None:
        return 1
    if isinstance(axis, str):
        return mesh.shape[axis] if axis in mesh.shape else 0
    return math.prod(mesh.shape[a] for a in axis if a in mesh.shape)


def _resolve(mesh: Mesh, rules: dict, logical: Tuple[Optional[str], ...],
             shape: Tuple[int, ...]) -> P:
    spec = []
    used = set()
    for name, dim in zip(logical, shape):
        axis = rules.get(name) if name is not None else None
        if axis is not None:
            if isinstance(axis, tuple):
                axis = tuple(a for a in axis if a in mesh.shape and a not in used)
                axis = axis or None
            elif axis not in mesh.shape or axis in used:
                axis = None
        if axis is not None:
            size = _mesh_axis_size(mesh, axis)
            if size <= 1 or dim % size != 0:
                axis = None  # divisibility guard
        if axis is not None:
            for a in (axis if isinstance(axis, tuple) else (axis,)):
                used.add(a)
        if isinstance(axis, tuple) and len(axis) == 1:
            axis = axis[0]  # P(('x',)) != P('x') on older jax
        spec.append(axis)
    while spec and spec[-1] is None:
        spec.pop()
    return P(*spec)


def mesh_active() -> bool:
    """Whether ``use_mesh`` holds a mesh of more than one device."""
    mesh = _ctx().mesh
    return mesh is not None and mesh.size > 1


def constrain(x, *logical: Optional[str]):
    """Apply a logical-axis sharding constraint to an activation. No-op
    without an active mesh (single-device smoke tests)."""
    if not mesh_active():
        return x
    ctx = _ctx()
    if len(logical) != x.ndim:
        raise ValueError(f"rank mismatch: {logical} vs {x.shape}")
    spec = _resolve(ctx.mesh, ctx.act_rules, logical, x.shape)
    return jax.lax.with_sharding_constraint(x, NamedSharding(ctx.mesh, spec))


def param_sharding(shape: Tuple[int, ...], logical: Tuple[Optional[str], ...],
                   mesh: Mesh) -> NamedSharding:
    ctx = _ctx()
    spec = _resolve(mesh, ctx.param_rules, logical, shape)
    return NamedSharding(mesh, spec)


def param_pspec(shape, logical, mesh) -> P:
    return _resolve(_ctx().mesh or mesh, _ctx().param_rules, logical, shape)


# ---------------------------------------------------------------------------
# profiling-stack shardings (mesh-parallel truncate / mem-mode / autosearch)
# ---------------------------------------------------------------------------
# The sharded profiling path partitions work along exactly two axes:
#   * the CANDIDATE axis — the leading K axis of a (K, num_sites, 4) format
#     table batch. Each candidate policy is independent, so sharding K over
#     `probe_axis` evaluates K/ndev candidates per device concurrently.
#   * the DATA axis — ordinary data parallelism over the profiled inputs.
# The (num_sites, 4) table rows themselves are always replicated: every
# device sees its candidates' full site tables.

def replicated(mesh: Mesh) -> NamedSharding:
    """Fully-replicated sharding (format tables, small operands)."""
    return NamedSharding(mesh, P())


def probe_sharding(mesh: Mesh, axis: str = "probe") -> NamedSharding:
    """Shard the leading candidate axis of a table batch over ``axis``.

    Falls back to replication when the mesh has no such axis (so a
    data-only mesh can still call the sharded entry points)."""
    if axis not in mesh.shape:
        return replicated(mesh)
    return NamedSharding(mesh, P(axis))


def batch_sharding(mesh: Mesh, axis: str = "data") -> NamedSharding:
    """Shard the leading (batch) dim of profiled inputs over ``axis``."""
    if axis not in mesh.shape:
        return replicated(mesh)
    return NamedSharding(mesh, P(axis))


def probe_axis_size(mesh: Optional[Mesh], axis: str = "probe") -> int:
    """Number of shards the candidate axis is split into (1 = unsharded)."""
    if mesh is None or axis not in mesh.shape:
        return 1
    return int(mesh.shape[axis])


def pad_to_shards(n: int, mesh: Optional[Mesh], axis: str = "probe") -> int:
    """Round a candidate-batch width up so the leading axis divides evenly
    across the mesh's ``axis`` (GSPMD rejects uneven named shardings)."""
    size = probe_axis_size(mesh, axis)
    return -(-n // size) * size


def drop_padded_rows(tree, n_real: int):
    """Slice identity-padded rows off the leading (candidate) axis of every
    leaf of a batched result. Padded probes exist only to satisfy the fixed
    sharded signature — they must be masked out before results are read,
    compared, or merged, so padded and unpadded paths stay bit-identical."""
    return jax.tree_util.tree_map(lambda a: a[:n_real], tree)


def _is_sharding_leaf(x) -> bool:
    return (x is None or isinstance(x, P)
            or isinstance(x, jax.sharding.Sharding))


def flatten_arg_shardings(mesh: Optional[Mesh], in_shardings,
                          args, kwargs) -> Optional[list]:
    """Resolve a user-facing ``in_shardings`` to the flat per-leaf list the
    profiling callables jit with (their traced signature is one flat list
    of input leaves, not the original arguments).

    ``in_shardings`` follows jit's convention: a single sharding /
    ``PartitionSpec`` / ``None`` broadcasts to every POSITIONAL leaf, or a
    pytree prefix of the positional-args tuple whose entries broadcast over
    their argument's subtree (so ``[None, batch_sharding(mesh)]`` shards
    the whole second argument however deep its pytree is). Keyword-argument
    leaves always replicate (jit's in_shardings covers positional args
    only, and kwargs are typically scalars/config that can't take a spec).
    ``None`` entries and ``PartitionSpec`` entries resolve against ``mesh``
    (``None`` -> replicated); concrete ``Sharding`` objects pass through.
    Returns ``None`` when there is nothing to shard (no mesh and no
    shardings)."""
    if mesh is None and in_shardings is None:
        return None

    def resolve(s):
        if s is None:
            return NamedSharding(mesh, P()) if mesh is not None else None
        if isinstance(s, P):
            if mesh is None:
                raise ValueError("PartitionSpec in_shardings need a mesh= "
                                 "to resolve against")
            return NamedSharding(mesh, s)
        return s

    if _is_sharding_leaf(in_shardings):
        n_args = len(jax.tree_util.tree_leaves(tuple(args)))
        n_kw = len(jax.tree_util.tree_leaves(kwargs))
        return ([resolve(in_shardings)] * n_args
                + [resolve(None)] * n_kw)

    prefix = tuple(in_shardings) if isinstance(in_shardings, list) \
        else in_shardings
    flat: list = []
    # tree_map flattens ``prefix`` and hands each of its leaves the
    # CORRESPONDING SUBTREE of args (flatten_up_to semantics): one prefix
    # entry per argument, broadcast over that argument's leaves
    def spread(s, arg_subtree):
        n = jax.tree_util.tree_structure(arg_subtree).num_leaves
        flat.extend([resolve(s)] * n)
        return s

    try:
        jax.tree_util.tree_map(spread, prefix, tuple(args),
                               is_leaf=_is_sharding_leaf)
    except ValueError as e:
        raise ValueError(
            "in_shardings must be a single sharding/PartitionSpec/None or "
            f"a pytree prefix of the positional-args tuple: {e}") from e
    flat.extend([resolve(None)] * len(jax.tree_util.tree_leaves(kwargs)))
    return flat
