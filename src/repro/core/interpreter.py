"""Op-mode jaxpr interpreter — the JAX analogue of RAPTOR's LLVM pass.

``eval_quantized`` walks a jaxpr and re-binds every equation, rounding the
result of each matched floating-point primitive onto the policy's (e,m) grid
(compute-in-carrier + correctly-round-result = MPFR op-mode semantics, see
DESIGN.md §2). Because the walk happens *inside* a trace, the transformed
function can be jit'ed, differentiated, pjit-sharded, and scanned like any
other JAX function — the profiling instrument rides the normal compilation
pipeline just as RAPTOR rides LTO.

Two transforms share one walker:

  * **policy-driven** (``eval_quantized``): formats are trace-time constants
    — a new policy is a new trace + compile. Retains the static fast paths
    and the full rule feature set (masks, dot-input quantization).
  * **table-driven** (``eval_sites``): the walk only fixes *where* to
    quantize (the sites matched by a site policy); *what* format each site
    gets is a runtime ``(num_sites, 4)`` int32 table argument. One compile
    per input signature serves every candidate policy — swap the table, or
    ``vmap`` over a leading table axis to evaluate a whole ladder of
    policies in one batched call (see ``api.truncate_sweep``).

Higher-order primitives are handled recursively: ``jit``/``closed_call`` are
inlined; ``scan``/``while``/``cond`` are rebuilt through their high-level
APIs with transformed bodies; ``remat2`` is re-wrapped in ``jax.checkpoint``
(preserving memory behaviour); ``custom_jvp/vjp_call`` evaluate their primal
jaxpr (grad-then-truncate sees plain primitives anyway).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax._src import core as jcore
from jax._src import source_info_util

from repro.core.formats import parse_format
from repro.core.policy import TruncationPolicy, TruncationRule, join_stack


def _safe_map(f, *xs):
    ls = [list(x) for x in xs]
    assert len({len(l) for l in ls}) == 1, 'length mismatch'
    return list(map(f, *ls))
from repro.kernels import fp8_dot as _fp8
from repro.kernels.fused import fused_outputs
from repro.kernels.quantize_em.ops import (
    quantize, quantize_dynamic, quantize_prepared, prepare_dynamic,
    format_row, IDENTITY_ROW,
)

# primitives whose *inputs* we optionally quantize to emulate a low-precision
# matrix unit with full-precision accumulation (TPU-realistic scenario)
_DOT_PRIMS = frozenset({"dot_general", "conv_general_dilated", "ragged_dot"})


def _maybe_quantize(val, rule: TruncationRule, impl: str):
    if not isinstance(val, jax.Array) and not hasattr(val, "dtype"):
        return val
    if not jnp.issubdtype(val.dtype, jnp.floating):
        return val
    q = quantize(val, rule.fmt, impl=impl)
    if rule.mask is not None:
        q = jnp.where(rule.mask(val), q, val)
    return q


# --------------------------------------------------------------------------
# per-equation transform contexts
# --------------------------------------------------------------------------

class _PolicyCtx:
    """Trace-time-constant formats: the original op-mode transform."""

    __slots__ = ("policy", "impl", "live", "native_fp8")

    def __init__(self, policy: TruncationPolicy, impl: str,
                 native_fp8: bool = False):
        self.policy = policy
        self.impl = impl
        self.native_fp8 = native_fp8
        # fast path: a policy with no rules can never match — skip the
        # per-equation-per-outvar matcher calls entirely (they are the
        # dominant python cost of walking big jaxprs; see test_interpreter).
        self.live = bool(policy.rules)

    def eqn_outputs(self, jaxpr, eqn_idx, eqn, invals, name_stack):
        prim = eqn.primitive
        rule0 = None
        if self.live and prim.name in _DOT_PRIMS and eqn.outvars:
            rule0 = self.policy.rule_for(name_stack, prim.name,
                                         eqn.outvars[0].aval.dtype)
            if rule0 is not None and rule0.quantize_dot_inputs:
                fp8 = self._native_fp8_rule(rule0, prim, eqn)
                if fp8 is not None:
                    return [_fp8.fp8_dot_general(
                        invals[0], invals[1],
                        eqn.params["dimension_numbers"],
                        saturate=fp8.saturate,
                        precision=eqn.params.get("precision"),
                        out_dtype=eqn.outvars[0].aval.dtype)]
                invals = [_maybe_quantize(v, rule0, self.impl) for v in invals]
        routed = ()
        if self.live:
            fused_outs = fused_outputs(eqn)
            if fused_outs is not None and len(fused_outs) == 1:
                fi = fused_outs[0]
                rule = self.policy.rule_for(name_stack, prim.name,
                                            eqn.outvars[fi].aval.dtype)
                if (rule is not None and rule.mask is None
                        and not rule.quantize_dot_inputs):
                    # route the static rule into the kernel's fused epilogue:
                    # the format row replaces the scalar-prefetch operand and
                    # the separate quantize pass for that output is dropped
                    invals = [jnp.asarray(format_row(rule.fmt), jnp.int32),
                              *invals[1:]]
                    routed = (fi,)
        outvals = prim.bind(*invals, **eqn.params)
        if not prim.multiple_results:
            outvals = [outvals]
        outvals = list(outvals)
        if not self.live:
            return outvals
        for i, (ov, var) in enumerate(zip(outvals, eqn.outvars)):
            if i in routed:
                continue
            aval = var.aval
            if not hasattr(aval, "dtype"):
                continue
            rule = rule0 if rule0 is not None else self.policy.rule_for(
                name_stack, prim.name, aval.dtype)
            if rule is not None and jnp.issubdtype(aval.dtype, jnp.floating):
                if not (rule.quantize_dot_inputs and prim.name in _DOT_PRIMS):
                    outvals[i] = _maybe_quantize(ov, rule, self.impl)
        return outvals

    def _native_fp8_rule(self, rule, prim, eqn):
        """The parsed format when this dot eqn should take the native fp8
        execution path (e4m3-storable format, plain two-operand dot with a
        floating output), else None — emulated input quantize otherwise."""
        if not self.native_fp8 or prim.name != "dot_general":
            return None
        if rule.mask is not None or len(eqn.invars) != 2:
            return None
        fmt = parse_format(rule.fmt)
        if not _fp8.is_native_fp8_format(fmt):
            return None
        out_dt = eqn.outvars[0].aval.dtype
        if not jnp.issubdtype(out_dt, jnp.floating):
            return None
        return fmt


class _TableCtx:
    """Runtime-table formats: matching was pre-resolved into a SiteIndex, so
    the traced computation only carries static row indices into the traced
    ``table`` argument.

    On the ref impl (CPU, and every sweep) f32-carrier sites quantize
    through the prepared-table path: the format-field derivation runs once
    for the whole table (``prepare_dynamic``) and each site only slices its
    row and runs the array math — without this, hundreds of inlined
    derivations made the swept executable's one-off compile slower than
    recompiling the static transform per candidate. The prep is derived
    EAGERLY here, at the outer trace level: deriving it lazily at the first
    site leaked tracers when that site sat inside a scan/while body (the
    cached arrays belonged to the body's inner trace but outlived it).
    Inner-scope sites closing over the outer-level prep is plain closure
    capture and fine. f64 sites (rare: x64 oracle runs) and the pallas
    impls keep the per-site ``quantize_dynamic`` call."""

    __slots__ = ("table", "index", "impl", "_prep32")

    def __init__(self, table, index: "SiteIndex", impl: str):
        self.table = table
        self.index = index
        self.impl = impl
        self._prep32 = prepare_dynamic(table, jnp.float32)

    def _quantize_site(self, val, site: int):
        impl = self.impl
        if impl == "auto":
            impl = "pallas" if jax.default_backend() == "tpu" else "ref"
        if (impl != "ref" or not hasattr(val, "dtype")
                or jnp.dtype(val.dtype) == jnp.dtype(jnp.float64)):
            return quantize_dynamic(val, self.table[site], impl=impl)
        if not jnp.issubdtype(jnp.dtype(val.dtype), jnp.floating):
            return val
        return quantize_prepared(val, self._prep32, site)

    def eqn_outputs(self, jaxpr, eqn_idx, eqn, invals, name_stack):
        prim = eqn.primitive
        routed = ()
        fused_outs = fused_outputs(eqn)
        if fused_outs is not None and len(fused_outs) == 1:
            fi = fused_outs[0]
            site = self.index.lookup(jaxpr, eqn_idx, fi, name_stack)
            if site is not None:
                # route the site's table row into the kernel's fused quantize
                # epilogue (replacing the scalar-prefetch operand) instead of
                # appending a separate quantize kernel after the call
                invals = [jnp.asarray(self.table[site], jnp.int32),
                          *invals[1:]]
                routed = (fi,)
        outvals = prim.bind(*invals, **eqn.params)
        if not prim.multiple_results:
            outvals = [outvals]
        outvals = list(outvals)
        for i in range(len(outvals)):
            if i in routed:
                continue
            site = self.index.lookup(jaxpr, eqn_idx, i, name_stack)
            if site is not None:
                outvals[i] = self._quantize_site(outvals[i], site)
        return outvals


def _jit_sharded(fn, flat_shardings):
    """``jax.jit`` over a flat-leaf-list callable with pre-resolved per-leaf
    shardings (the output of ``distributed.sharding.flatten_arg_shardings``;
    ``None`` = no sharding constraints)."""
    if flat_shardings is None:
        return jax.jit(fn)
    return jax.jit(fn, in_shardings=(flat_shardings,))


def quantized_callable(closed: jcore.ClosedJaxpr, out_tree,
                       policy: TruncationPolicy, impl: str = "auto",
                       *, flat_shardings=None, native_fp8: bool = False):
    """jit-close the transformed computation once. The jaxpr walk (and its
    per-equation policy matching) happens a single time, at trace; every
    subsequent call with the same avals hits XLA's executable cache, so
    repeated evaluations — the precision-search inner loop — pay only the
    kernel launch, not a re-interpretation.

    ``flat_shardings`` (pre-resolved per-leaf, see ``distributed.sharding.
    flatten_arg_shardings``) GSPMD-partition the executable: inputs are
    placed per the shardings and the truncated computation runs
    data-parallel across the mesh — profiling rides the normal SPMD
    pipeline, formats and semantics unchanged."""
    def run(flat):
        outs = eval_quantized(closed.jaxpr, closed.consts, list(flat),
                              policy, impl, native_fp8=native_fp8)
        return jax.tree_util.tree_unflatten(out_tree, outs)

    return _jit_sharded(run, flat_shardings)


def rebinding_scope(eqn):
    """Rebind ``eqn`` under its own name stack, joined to the one it is
    traced in: the compiled program then keeps each op's scopes in its
    metadata, and a Pallas kernel, whose op is named after the innermost
    scope, keeps its name."""
    return source_info_util.set_name_stack(
        source_info_util.current_name_stack() + eqn.source_info.name_stack)


def eval_quantized(jaxpr: jcore.Jaxpr, consts: Sequence[Any], args: Sequence[Any],
                   policy: TruncationPolicy, impl: str = "auto",
                   prefix: str = "", *, native_fp8: bool = False) -> List[Any]:
    """Evaluate ``jaxpr`` with op-mode truncation under ``policy``.

    ``native_fp8``: run ``quantize_dot_inputs`` dot sites whose format maps
    onto ``float8_e4m3fn`` on the native fp8 execution path (fp8 storage,
    f32 accumulation) instead of emulating the rounding in the carrier."""
    return _eval(jaxpr, consts, args, _PolicyCtx(policy, impl, native_fp8),
                 prefix)


def _eval(jaxpr: jcore.Jaxpr, consts: Sequence[Any], args: Sequence[Any],
          ctx, prefix: str = "") -> List[Any]:
    env = {}

    def read(v):
        return v.val if isinstance(v, jcore.Literal) else env[v]

    def write(v, val):
        env[v] = val

    _safe_map(write, jaxpr.constvars, consts)
    _safe_map(write, jaxpr.invars, args)

    for eqn_idx, eqn in enumerate(jaxpr.eqns):
        invals = [read(v) for v in eqn.invars]
        prim = eqn.primitive
        name_stack = join_stack(prefix, str(eqn.source_info.name_stack))
        handler = _HOP_HANDLERS.get(prim.name)
        with rebinding_scope(eqn):
            if handler is not None:
                outvals = handler(eqn, invals, ctx, name_stack)
            else:
                outvals = ctx.eqn_outputs(jaxpr, eqn_idx, eqn, invals,
                                          name_stack)
        if not isinstance(outvals, (list, tuple)):
            outvals = [outvals]
        _safe_map(write, eqn.outvars, outvals)

    return [read(v) for v in jaxpr.outvars]


# --------------------------------------------------------------------------
# quantize-site enumeration (runtime-parameterized formats)
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class QuantizeSite:
    """One policy-matched (equation, output) position in the jaxpr forest.

    ``stack`` is the raw (un-normalized) joined name stack exactly as the
    walker sees it, so re-matching a candidate policy against the site
    reproduces the static transform's decision bit-for-bit."""

    index: int
    stack: str
    prim: str
    dtype: Any

    @property
    def scope(self) -> str:
        from repro.core.policy import normalize_stack
        return normalize_stack(self.stack)


class SiteIndex:
    """Order-stable site enumeration for one traced computation.

    Maps (sub-jaxpr identity, eqn position, outvar position, name stack) ->
    row of the runtime format table. The name stack is part of the key
    because jax's tracing caches share sub-jaxpr *objects* across call
    sites: one jitted helper called under two scopes is a single
    ClosedJaxpr reached with two different stack prefixes, and each prefix
    needs its own policy-matched rows. The jaxpr objects are pinned so the
    id()-based keys can never be recycled while the index is alive."""

    def __init__(self, sites: List[QuantizeSite], by_key: Dict, pinned: List):
        self.sites = sites
        self._by_key = by_key
        self._pinned = pinned

    def __len__(self) -> int:
        return len(self.sites)

    def lookup(self, jaxpr, eqn_idx: int, out_idx: int,
               name_stack: str) -> Optional[int]:
        return self._by_key.get((id(jaxpr), eqn_idx, out_idx, name_stack))

    def identity_table(self) -> np.ndarray:
        """The (num_sites, 4) table that quantizes nothing."""
        return np.tile(IDENTITY_ROW, (len(self.sites), 1))

    def site_keys(self) -> List[Tuple]:
        """Per-site lookup keys, in site order — the inverse of the
        ``(id(jaxpr), eqn_idx, out_idx, name_stack) -> row`` mapping.
        Lets analyses built over the same jaxpr forest (``repro.analysis``)
        address their per-value records by site."""
        keys: List = [None] * len(self.sites)
        for k, i in self._by_key.items():
            keys[i] = k
        return keys

    def table_for(self, policy: TruncationPolicy) -> np.ndarray:
        """Lower a candidate policy to its (num_sites, 4) int32 format table.

        Sites the policy does not match get the identity row; matched sites
        get the matching rule's format. Raises for rules the runtime path
        cannot represent (masks, dot-input quantization)."""
        rows = np.tile(IDENTITY_ROW, (len(self.sites), 1))
        for s in self.sites:
            rule = policy.rule_for(s.stack, s.prim, s.dtype)
            if rule is None:
                continue
            if rule.mask is not None or rule.quantize_dot_inputs:
                raise ValueError(
                    "runtime format tables support plain output-quantize "
                    f"rules only (offending rule scope={rule.scope!r})")
            rows[s.index] = format_row(rule.fmt)
        return rows


def enumerate_sites(closed: jcore.ClosedJaxpr,
                    site_policy: TruncationPolicy) -> SiteIndex:
    """Single structural walk enumerating every quantize site the
    ``site_policy`` matches, in the same traversal order as the evaluator.

    The site policy fixes *where* quantization may happen (its formats are
    irrelevant); any candidate policy whose matched set is a subset of the
    site policy's can then be lowered to a table via ``table_for``."""
    for r in site_policy.rules:
        if r.mask is not None or r.quantize_dot_inputs:
            raise ValueError("site policies support plain output-quantize "
                             "rules only")

    sites: List[QuantizeSite] = []
    by_key: Dict = {}
    pinned: List = []
    seen: set = set()

    def walk(jaxpr: jcore.Jaxpr, prefix: str) -> None:
        # a shared sub-jaxpr object must be walked once per distinct prefix:
        # each call site carries its own stack and may match different rules
        # (two call sites with an identical prefix collapse to the same
        # keys/rows, which is exactly the static transform's decision too)
        if (id(jaxpr), prefix) in seen:
            return
        seen.add((id(jaxpr), prefix))
        pinned.append(jaxpr)
        for eqn_idx, eqn in enumerate(jaxpr.eqns):
            pname = eqn.primitive.name
            name_stack = join_stack(prefix, str(eqn.source_info.name_stack))
            if pname in _HOP_HANDLERS:
                if pname == "cond":
                    for br in eqn.params["branches"]:
                        walk(_closed(br).jaxpr, name_stack)
                elif pname == "while":
                    walk(_closed(eqn.params["cond_jaxpr"]).jaxpr, name_stack)
                    walk(_closed(eqn.params["body_jaxpr"]).jaxpr, name_stack)
                else:
                    key = ("call_jaxpr" if "call_jaxpr" in eqn.params
                           else "jaxpr")
                    walk(_closed(eqn.params[key]).jaxpr, name_stack)
                continue
            for out_idx, var in enumerate(eqn.outvars):
                aval = var.aval
                if (not hasattr(aval, "dtype")
                        or not jnp.issubdtype(aval.dtype, jnp.floating)):
                    continue
                if site_policy.rule_for(name_stack, pname, aval.dtype) is None:
                    continue
                site = QuantizeSite(len(sites), name_stack, pname, aval.dtype)
                by_key[(id(jaxpr), eqn_idx, out_idx, name_stack)] = site.index
                sites.append(site)

    pinned.append(closed)  # keep consts/jaxpr alive alongside the ids
    walk(closed.jaxpr, "")
    return SiteIndex(sites, by_key, pinned)


def eval_sites(jaxpr: jcore.Jaxpr, consts: Sequence[Any], args: Sequence[Any],
               table, index: SiteIndex, impl: str = "auto") -> List[Any]:
    """Evaluate ``jaxpr`` quantizing each enumerated site onto the format in
    its ``table`` row — the runtime-parameterized twin of
    ``eval_quantized``."""
    return _eval(jaxpr, consts, args, _TableCtx(table, index, impl), "")


def parameterized_callable(closed: jcore.ClosedJaxpr, out_tree,
                           index: SiteIndex, impl: str = "auto",
                           *, mesh=None, batch_axis: str = "probe",
                           flat_shardings=None):
    """Compile-once runtime-parameterized transform.

    Returns ``(run, run_batch)``: ``run(table, flat)`` evaluates one
    candidate format table; ``run_batch(tables, flat)`` vmaps over a leading
    candidate axis, evaluating a whole ladder of policies in one batched
    call. Either is compiled once per input signature — a new candidate
    policy is just a new table value.

    With ``mesh`` the leading K (candidate) axis of ``tables`` is split
    over ``mesh.shape[batch_axis]`` devices — a W-candidate ladder evaluates
    on W/ndev devices concurrently — while each candidate's
    ``(num_sites, 4)`` table rows stay whole. The split is a ``shard_map``
    over ``batch_axis``, not GSPMD: each device runs its own candidates
    through the unpartitioned program, since GSPMD cannot partition the
    Pallas quantize kernels. Profiled inputs enter whole on that axis and
    follow ``flat_shardings`` (pre-resolved per-leaf, see
    ``distributed.sharding.flatten_arg_shardings``; default replicated) on
    any other. K must divide evenly across the axis — pad ladders with
    ``index.identity_table()`` rows (``distributed.sharding.pad_to_shards``)
    and drop the padded outputs."""
    def _run(table, flat):
        outs = eval_sites(closed.jaxpr, closed.consts, list(flat),
                          jnp.asarray(table, jnp.int32), index, impl)
        return jax.tree_util.tree_unflatten(out_tree, outs)

    vb = jax.vmap(_run, in_axes=(0, None))
    if mesh is None and flat_shardings is None:
        return jax.jit(_run), jax.jit(vb)

    from jax.sharding import PartitionSpec as P

    from repro.distributed.sharding import probe_sharding, replicated

    if mesh is not None:
        data_sh = (flat_shardings if flat_shardings is not None
                   else replicated(mesh))
        table_sh = probe_sharding(mesh, batch_axis)
        repl = replicated(mesh)
        # check_vma off: pallas_call out_shapes carry no varying-axes info
        vb = jax.shard_map(vb, mesh=mesh, in_specs=(P(batch_axis), P()),
                           out_specs=P(batch_axis), axis_names={batch_axis},
                           check_vma=False)
    else:  # concrete shardings given, no mesh for the table axis
        data_sh = flat_shardings
        table_sh = repl = None
    run = jax.jit(_run, in_shardings=(repl, data_sh))
    run_batch = jax.jit(vb, in_shardings=(table_sh, data_sh))
    return run, run_batch


# --------------------------------------------------------------------------
# higher-order primitive handlers
# --------------------------------------------------------------------------

def _closed(eqn_param) -> jcore.ClosedJaxpr:
    if isinstance(eqn_param, jcore.ClosedJaxpr):
        return eqn_param
    return jcore.ClosedJaxpr(eqn_param, ())


def _handle_call(eqn, invals, ctx, prefix):
    key = "call_jaxpr" if "call_jaxpr" in eqn.params else "jaxpr"
    closed = _closed(eqn.params[key])
    return _eval(closed.jaxpr, closed.consts, invals, ctx, prefix)


def _handle_scan(eqn, invals, ctx, prefix):
    p = eqn.params
    closed = _closed(p["jaxpr"])
    nc, ncarry = p["num_consts"], p["num_carry"]
    body_consts = invals[:nc]
    carry_in = tuple(invals[nc:nc + ncarry])
    xs = tuple(invals[nc + ncarry:])

    def body_fn(carry, x):
        res = _eval(closed.jaxpr, closed.consts,
                    list(body_consts) + list(carry) + list(x), ctx, prefix)
        return tuple(res[:ncarry]), tuple(res[ncarry:])

    carry_out, ys = lax.scan(body_fn, carry_in, xs, length=p["length"],
                             reverse=p["reverse"], unroll=p["unroll"])
    return list(carry_out) + list(ys)


def _handle_while(eqn, invals, ctx, prefix):
    p = eqn.params
    cond_closed = _closed(p["cond_jaxpr"])
    body_closed = _closed(p["body_jaxpr"])
    cn, bn = p["cond_nconsts"], p["body_nconsts"]
    cond_consts = invals[:cn]
    body_consts = invals[cn:cn + bn]
    carry_in = tuple(invals[cn + bn:])

    def cond_fn(carry):
        res = _eval(cond_closed.jaxpr, cond_closed.consts,
                    list(cond_consts) + list(carry), ctx, prefix)
        return res[0]

    def body_fn(carry):
        res = _eval(body_closed.jaxpr, body_closed.consts,
                    list(body_consts) + list(carry), ctx, prefix)
        return tuple(res)

    out = lax.while_loop(cond_fn, body_fn, carry_in)
    return list(out)


def _handle_cond(eqn, invals, ctx, prefix):
    branches = eqn.params["branches"]
    index, *operands = invals

    def make_branch(br):
        closed = _closed(br)
        return lambda *ops: tuple(
            _eval(closed.jaxpr, closed.consts, list(ops), ctx, prefix))

    out = lax.switch(index, [make_branch(b) for b in branches], *operands)
    return list(out)


def _handle_remat(eqn, invals, ctx, prefix):
    closed = _closed(eqn.params["jaxpr"])

    @functools.partial(jax.checkpoint, policy=eqn.params.get("policy"),
                       prevent_cse=eqn.params.get("prevent_cse", True))
    def inner(*args):
        return tuple(_eval(closed.jaxpr, closed.consts, list(args), ctx,
                           prefix))

    return list(inner(*invals))


def _handle_custom_call(eqn, invals, ctx, prefix):
    closed = _closed(eqn.params["call_jaxpr"])
    return _eval(closed.jaxpr, closed.consts, invals, ctx, prefix)


_HOP_HANDLERS = {
    "jit": _handle_call,
    "pjit": _handle_call,
    "closed_call": _handle_call,
    "core_call": _handle_call,
    "scan": _handle_scan,
    "while": _handle_while,
    "cond": _handle_cond,
    "remat2": _handle_remat,
    "checkpoint": _handle_remat,
    "custom_jvp_call": _handle_custom_call,
    "custom_vjp_call": _handle_custom_call,
    "custom_vjp_call_jaxpr": _handle_custom_call,
}
