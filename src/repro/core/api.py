"""Public RAPTOR-JAX API.

    from repro.core import api as raptor

    policy = raptor.TruncationPolicy.scoped("model/*/mlp", "e5m7")
    lossy_step = raptor.truncate(train_step, policy)       # op-mode
    out, report = raptor.memtrace(step, policy,
                                  threshold=1e-3)(...)     # mem-mode
    counts = raptor.profile_counts(step, policy)(...)      # speedup inputs

Op-mode and mem-mode wrappers cache the transformed, ``jax.jit``-closed
computation keyed on (input pytree structure, input avals, policy identity):
the jaxpr is walked and the policy matched once per distinct signature, and
every further call is a compiled-executable dispatch. This is what makes the
automated precision search (``repro.search``) affordable — each candidate
policy costs one trace, each repeat evaluation costs ~a kernel launch.

``truncate_sweep`` goes one step further: the cache is keyed on quantize
*sites* rather than policy identity, and the formats become a runtime
``(num_sites, 4)`` table argument. One compile per input signature serves
every candidate policy — a new policy is a new table, and a whole ladder of
policies evaluates in one ``vmap``-batched call. That is the zero-recompile
policy-sweep path the batched precision search runs on.

Canonical surface — one shape for every transform. Positional arguments are
``(fn, policy)`` only; everything else is a keyword-only tail shared across
the surface (``memtrace``'s historical positional ``threshold`` is accepted
behind a deprecation shim):

    transform            returns                    keyword-only tail
    -------------------  -------------------------  ---------------------------
    truncate             fn'                        impl, cache, mesh,
                                                    in_shardings
    truncate_sweep       SweepHandle factory        impl, cache, mesh,
                                                    batch_axis, in_shardings
    memtrace             (out, RaptorReport)        threshold, impl, cache,
                                                    mesh, in_shardings
    profile_trajectory   (out, TrajectoryReport)    threshold, n_steps, impl,
                                                    cache, mesh, in_shardings
    profile_counts       CountReport                cache, mesh, in_shardings

All five trace-cache per input signature and expose ``n_traces`` /
``cache_size()`` / ``cache_clear()``. ``mesh``/``in_shardings`` partition
the cached executable across a device mesh (``profile_counts`` accepts them
for surface uniformity; static counts are partition-invariant).
"""
from __future__ import annotations

import functools
import warnings
from typing import Callable

import numpy as np

import jax
from jax._src import core as jcore

from repro.core import interpreter, memmode, counters
from repro.core.formats import FPFormat, parse_format  # re-export
from repro.core.policy import (  # re-export
    TruncationPolicy, TruncationRule, magnitude_below, magnitude_above,
)

scope = jax.named_scope  # region marker, the _raptor_trunc_func_* analogue


def _flatten_like_make_jaxpr(args, kwargs):
    return jax.tree_util.tree_leaves((args, kwargs))


def _leaf_key(x):
    """Cache-key component for one input leaf: shape + dtype + weak_type
    (python scalars and weak-typed arrays promote differently than strong
    arrays of the same dtype, so they must not share a cache entry)."""
    if hasattr(x, "shape") and hasattr(x, "dtype"):
        return (tuple(x.shape), str(x.dtype),
                bool(getattr(x, "weak_type", False)))
    return (np.shape(x), str(np.result_type(x)), True)


def _has_tracer(xs) -> bool:
    return any(isinstance(x, jcore.Tracer) for x in xs)


def _mesh_key(mesh, in_shardings, *extra) -> tuple:
    """Hashable cache-key component for a (mesh, shardings) pair. Shardings
    may arrive as an arbitrary pytree (lists are unhashable), so flatten to
    (treedef, leaves) — NamedSharding/PartitionSpec leaves hash fine."""
    if mesh is None and in_shardings is None and not any(extra):
        return (None,)
    leaves, tree = jax.tree_util.tree_flatten(
        in_shardings, is_leaf=lambda x: x is None)
    return (mesh, tree, tuple(leaves)) + extra


def _signature_key(in_tree, leaves, suffix: tuple) -> tuple:
    """The shared trace-cache key scheme: input pytree structure + per-leaf
    aval signature + transform identity. Both the policy-keyed caches
    (truncate/memtrace) and the sites-keyed cache (truncate_sweep) use this
    so leaf/weak-type semantics can never diverge between them."""
    return (in_tree, tuple(_leaf_key(l) for l in leaves)) + suffix


def _cached_transform(fn: Callable, build: Callable, fallback: Callable,
                      key_suffix: tuple, cache: bool) -> Callable:
    """Shared trace-cache machinery for ``truncate``/``memtrace``.

    ``build(closed, out_tree, args, kwargs)`` -> jit-closed callable taking
    flat leaves (args/kwargs are the example call, for resolving
    per-argument shardings against the input structure);
    ``fallback(closed, out_tree, leaves)`` -> direct (uncached) evaluation,
    used under an outer trace where caching a jaxpr would leak tracers.
    """
    name = getattr(fn, "__qualname__", type(fn).__name__)

    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        leaves, in_tree = jax.tree_util.tree_flatten((args, kwargs))
        use_cache = cache and not _has_tracer(leaves)
        key = None
        if use_cache:
            key = _signature_key(in_tree, leaves, key_suffix)
            entry = wrapped._cache.get(key)
            if entry is not None:
                return entry(leaves)
        wrapped.n_traces += 1
        # a miss traces, and its first call compiles: a span of its own in
        # the profiler's trace, so that the time is not put down to its caller
        with jax.profiler.TraceAnnotation("raptor/api.trace", fn=name,
                                          n_traces=wrapped.n_traces):
            closed, out_shape = jax.make_jaxpr(fn, return_shape=True)(
                *args, **kwargs)
            out_tree = jax.tree_util.tree_structure(out_shape)
            if not use_cache or _has_tracer(closed.consts):
                return fallback(closed, out_tree, leaves)
            entry = build(closed, out_tree, args, kwargs)
            wrapped._cache[key] = entry
            return entry(leaves)

    wrapped._cache = {}
    wrapped.n_traces = 0          # times the jaxpr walk actually ran
    wrapped.cache_clear = wrapped._cache.clear
    wrapped.cache_size = lambda: len(wrapped._cache)
    return wrapped


def truncate(fn: Callable, policy: TruncationPolicy, *, impl: str = "auto",
             cache: bool = True, mesh=None, in_shardings=None,
             native_fp8: bool = False) -> Callable:
    """Return ``fn`` with op-mode truncation applied under ``policy``.

    The wrapper is an ordinary traceable JAX function: compose freely with
    ``jax.jit``, ``jax.grad`` (grad-then-truncate covers the backward pass),
    ``shard_map``/``pjit`` meshes, etc. Under an outer trace it falls back to
    direct interpretation; called concretely it reuses a jit-closed transform
    per input signature (``wrapper.n_traces`` counts actual jaxpr walks).

    ``mesh``/``in_shardings`` SPMD-partition the cached executable: inputs
    are placed per the shardings (jit's convention — a single sharding or
    ``PartitionSpec`` broadcasts to every leaf, or a pytree prefix of the
    positional-args tuple; ``None`` replicates) and the truncated
    computation runs data-parallel across the mesh. The fallback path under
    an outer trace ignores them (the enclosing jit owns the partitioning).

    ``native_fp8``: execute ``quantize_dot_inputs`` dot sites whose rule
    format maps onto ``float8_e4m3fn`` (e4m3, fn overflow) on native fp8
    storage with f32 accumulation (``repro.kernels.fp8_dot``) instead of
    emulating the rounding in the carrier dtype — same bit-exact input
    quantize, but the contraction actually exercises the low-precision
    unit."""
    from repro.distributed.sharding import flatten_arg_shardings

    def build(closed, out_tree, bargs, bkwargs):
        return interpreter.quantized_callable(
            closed, out_tree, policy, impl, native_fp8=native_fp8,
            flat_shardings=flatten_arg_shardings(
                mesh, in_shardings, bargs, bkwargs))

    def fallback(closed, out_tree, leaves):
        outs = interpreter.eval_quantized(
            closed.jaxpr, closed.consts, leaves, policy, impl,
            native_fp8=native_fp8)
        return jax.tree_util.tree_unflatten(out_tree, outs)

    return _cached_transform(
        fn, build, fallback,
        (policy.cache_key(), impl, native_fp8,
         _mesh_key(mesh, in_shardings)), cache)


class SweepHandle:
    """One input signature's runtime-parameterized executable plus its site
    layout. Every candidate policy runs through the same compiled callable —
    only the ``(num_sites, 4)`` int32 format table changes.

    * ``handle(table)`` — evaluate one candidate table.
    * ``handle.batch(tables)`` — evaluate a ``(K, num_sites, 4)`` stack of
      candidates in one vmapped call (outputs gain a leading K axis).
    * ``handle.table(policy)`` — lower a :class:`TruncationPolicy` to its
      table (unmatched sites get the identity row).

    Under a sharded sweep (``truncate_sweep(..., mesh=...)``) the leading K
    axis of ``batch`` is partitioned across the mesh's probe axis; ladders
    whose K doesn't divide the axis are padded with identity rows and the
    padded outputs sliced off, so results are positionally identical to the
    unsharded path.
    """

    def __init__(self, index, run, run_batch, leaves, shard_multiple=1):
        self._index = index
        self._run = run
        self._run_batch = run_batch
        self._leaves = leaves
        self._shard_multiple = shard_multiple

    @property
    def sites(self):
        return self._index.sites

    @property
    def num_sites(self) -> int:
        return len(self._index)

    def table(self, policy: TruncationPolicy) -> np.ndarray:
        return self._index.table_for(policy)

    def tables(self, policies) -> np.ndarray:
        """Stack several candidate policies into a (K, num_sites, 4) batch."""
        return np.stack([self._index.table_for(p) for p in policies])

    def identity_table(self) -> np.ndarray:
        return self._index.identity_table()

    def __call__(self, table):
        return self._run(table, self._leaves)

    def batch(self, tables):
        from repro.distributed.sharding import drop_padded_rows
        k = int(np.shape(tables)[0])
        mult = self._shard_multiple
        pad = -k % mult
        if pad:
            tables = np.concatenate(
                [np.asarray(tables),
                 np.tile(self._index.identity_table(), (pad, 1, 1))])
        outs = self._run_batch(tables, self._leaves)
        if pad:
            outs = drop_padded_rows(outs, k)
        return outs


def truncate_sweep(fn: Callable, site_policy: TruncationPolicy, *,
                   impl: str = "auto", cache: bool = True, mesh=None,
                   batch_axis: str = "probe",
                   in_shardings=None) -> Callable:
    """Runtime-parameterized op-mode: compile once, sweep policies for free.

    ``site_policy`` fixes *where* quantization may happen — every equation
    output it matches becomes an indexed quantize site (its formats are
    irrelevant; use e.g. ``TruncationPolicy.everywhere("e5m2")`` for "any
    float op", or one rule per search scope). Calling the returned wrapper
    with concrete inputs yields a :class:`SweepHandle` bound to those
    inputs; any candidate policy whose matched set is a subset of the site
    policy's lowers to a format table and evaluates WITHOUT retracing or
    recompiling. ``wrapper.n_traces`` counts actual jaxpr walks (one per
    input signature).

    ``mesh`` makes the sweep candidate-parallel: ``handle.batch`` shards the
    leading K (candidate) axis over ``mesh.shape[batch_axis]`` devices —
    table rows replicated, inputs placed per ``in_shardings`` (default
    replicated) — so a W-candidate ladder evaluates on W/ndev devices
    concurrently. Results stay bit-for-bit identical to the unsharded path
    (ladders are identity-padded to the shard multiple and sliced back)."""
    def wrapped(*args, **kwargs) -> SweepHandle:
        leaves, in_tree = jax.tree_util.tree_flatten((args, kwargs))
        if _has_tracer(leaves):
            raise TypeError(
                "truncate_sweep handles concrete inputs only; compose "
                "jit/grad with `truncate` instead")
        key = _signature_key(
            in_tree, leaves,
            (site_policy.cache_key(), impl,
             _mesh_key(mesh, in_shardings, batch_axis)))
        entry = wrapped._cache.get(key) if cache else None
        if entry is None:
            wrapped.n_traces += 1
            closed, out_shape = jax.make_jaxpr(
                fn, return_shape=True)(*args, **kwargs)
            if _has_tracer(closed.consts):
                # a closure captured a tracer from an enclosing trace: the
                # handle would outlive that trace, and caching it would
                # poison every later concrete call of the same signature
                raise TypeError(
                    "truncate_sweep traced a function that closes over a "
                    "value from an enclosing jit/grad trace; call it "
                    "outside the trace or pass the value as an argument")
            out_tree = jax.tree_util.tree_structure(out_shape)
            index = interpreter.enumerate_sites(closed, site_policy)
            from repro.distributed.sharding import flatten_arg_shardings
            run, run_batch = interpreter.parameterized_callable(
                closed, out_tree, index, impl,
                mesh=mesh, batch_axis=batch_axis,
                flat_shardings=flatten_arg_shardings(
                    mesh, in_shardings, args, kwargs))
            entry = (index, run, run_batch)
            if cache:
                wrapped._cache[key] = entry
        index, run, run_batch = entry
        from repro.distributed.sharding import probe_axis_size
        return SweepHandle(index, run, run_batch, leaves,
                           shard_multiple=probe_axis_size(mesh, batch_axis))

    wrapped._cache = {}
    wrapped.n_traces = 0
    wrapped.cache_clear = wrapped._cache.clear
    wrapped.cache_size = lambda: len(wrapped._cache)
    return wrapped


def _legacy_threshold_shim(name: str, legacy, threshold: float) -> float:
    """One deprecation cycle for the historical positional ``threshold``:
    ``memtrace(fn, policy, 1e-4)`` keeps working but warns; the canonical
    spelling is keyword-only (``threshold=1e-4``), uniform across the
    surface table above."""
    if legacy is None:
        return threshold
    warnings.warn(
        f"{name}(fn, policy, threshold) with a positional threshold is "
        f"deprecated; pass threshold= as a keyword",
        DeprecationWarning, stacklevel=3)
    return float(legacy)


def memtrace(fn: Callable, policy: TruncationPolicy, _threshold=None,
             *, threshold: float = 1e-3, impl: str = "auto",
             cache: bool = True, mesh=None, in_shardings=None) -> Callable:
    """mem-mode: returns ``(outputs, RaptorReport)`` where the report carries
    per-source-location flag counts and max relative deviations of the
    truncated values against full-precision shadow values.

    ``mesh``/``in_shardings`` run the paired (truncated, shadow) evaluation
    data-parallel across a device mesh. The report stays EXACT under data
    parallelism: flag/op counts are global sums and max_rel a global max,
    reduced by XLA inside the partitioned executable — the thing RAPTOR's
    pointer-swizzled shadow structs cannot do across ranks (paper §6.3).
    For hand-rolled ``shard_map``/``pmap`` bodies, reduce per-shard reports
    with ``RaptorReport.allreduce(axis_name)`` (in-SPMD) or
    ``RaptorReport.merge_all(reports)`` (host-side)."""
    threshold = _legacy_threshold_shim("memtrace", _threshold, threshold)
    from repro.distributed.sharding import flatten_arg_shardings

    def build(closed, out_tree, bargs, bkwargs):
        return memmode.shadowed_callable(
            closed, out_tree, policy, threshold, impl,
            flat_shardings=flatten_arg_shardings(
                mesh, in_shardings, bargs, bkwargs))

    def fallback(closed, out_tree, leaves):
        outs, report = memmode.eval_shadowed(
            closed.jaxpr, closed.consts, leaves, policy, threshold, impl)
        return jax.tree_util.tree_unflatten(out_tree, outs), report

    return _cached_transform(
        fn, build, fallback,
        (policy.cache_key(), threshold, impl,
         _mesh_key(mesh, in_shardings)), cache)


def profile_trajectory(fn: Callable, policy: TruncationPolicy,
                       _threshold=None, *, threshold: float = 1e-3,
                       n_steps: int = 128, sites=None, impl: str = "auto",
                       cache: bool = True, mesh=None,
                       in_shardings=None) -> Callable:
    """Temporal mem-mode: returns ``(outputs, TrajectoryReport)`` where the
    report holds an ``(n_steps, n_loc)`` per-step deviation trajectory on
    top of the usual whole-run totals (see ``repro.profile.trajectory``).

    ``n_steps`` sizes the ring buffer; one row per iteration of the
    program's outermost ``scan``/``while`` loops (the app step loop — size
    it to ``MiniApp.n_steps`` for an exact trajectory; longer runs wrap).
    Inner solver loops accumulate into their enclosing step's row, and a
    straight-line program lands entirely in row 0.

    ``sites`` restricts the per-step trajectory to matching truncated sites
    (substring patterns over site location descriptions, same matching as
    ``TruncationPolicy`` rules): only matching sites get a trajectory
    column, which cuts the ring-buffer memory and per-step bookkeeping for
    wide tables to the handful of blamed sites under study. Whole-run
    totals still cover every truncated site; ``TrajectoryReport.columns``
    records the column -> location mapping. ``None`` keeps every site.

    Trace-cached and meshable exactly like ``memtrace``: with
    ``mesh``/``in_shardings`` the trajectory's sums/maxes are reduced by
    XLA's collectives inside the partitioned executable. Every signal the
    temporal analysis decides on — per-step max deviation, op counts, the
    step counter — is bit-identical to the single-device run (integer sums
    and float maxima are order-invariant); the float magnitude sums
    reproduce up to cross-shard summation order, the usual float-reduction
    contract. Hand-rolled ``shard_map`` bodies reduce with
    ``TrajectoryReport.allreduce``/``merge_all``."""
    threshold = _legacy_threshold_shim("profile_trajectory", _threshold,
                                       threshold)
    if n_steps < 1:
        raise ValueError(f"n_steps must be >= 1, got {n_steps}")
    from repro.distributed.sharding import flatten_arg_shardings

    def build(closed, out_tree, bargs, bkwargs):
        return memmode.shadowed_callable(
            closed, out_tree, policy, threshold, impl,
            traj_len=n_steps, traj_sites=sites,
            flat_shardings=flatten_arg_shardings(
                mesh, in_shardings, bargs, bkwargs))

    def fallback(closed, out_tree, leaves):
        outs, report = memmode.eval_shadowed(
            closed.jaxpr, closed.consts, leaves, policy, threshold, impl,
            traj_len=n_steps, traj_sites=sites)
        return jax.tree_util.tree_unflatten(out_tree, outs), report

    return _cached_transform(
        fn, build, fallback,
        ("trajectory", policy.cache_key(), threshold, impl, n_steps,
         tuple(sites) if sites is not None else None,
         _mesh_key(mesh, in_shardings)), cache)


def profile_counts(fn: Callable, policy: TruncationPolicy, *,
                   cache: bool = True, mesh=None,
                   in_shardings=None) -> Callable:
    """Static operation/byte counting (the paper's runtime counters, derived
    from the jaxpr instead): returns a CountReport of truncated vs
    full-precision FLOPs and bytes per scope.

    Trace-cached per input signature like the other transforms (counts are
    pure functions of the jaxpr, so a cache hit skips the trace + jaxpr walk
    entirely). ``mesh``/``in_shardings`` are accepted for surface uniformity
    and only contribute to the cache key — static counts are
    partition-invariant."""
    def wrapped(*args, **kwargs):
        leaves, in_tree = jax.tree_util.tree_flatten((args, kwargs))
        use_cache = cache and not _has_tracer(leaves)
        key = None
        if use_cache:
            key = _signature_key(
                in_tree, leaves,
                ("counts", policy.cache_key(), _mesh_key(mesh, in_shardings)))
            hit = wrapped._cache.get(key)
            if hit is not None:
                return hit
        wrapped.n_traces += 1
        closed = jax.make_jaxpr(fn)(*args, **kwargs)
        report = counters.count_jaxpr(closed.jaxpr, policy)
        if use_cache and not _has_tracer(closed.consts):
            wrapped._cache[key] = report
        return report

    wrapped._cache = {}
    wrapped.n_traces = 0
    wrapped.cache_clear = wrapped._cache.clear
    wrapped.cache_size = lambda: len(wrapped._cache)
    return wrapped
