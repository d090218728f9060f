"""Mem-mode: shadow-value tracking — the numerical debugger (paper §3.5/§6.3).

Every value flows through the computation as a pair ``(truncated, shadow)``.
The shadow lane replays the identical op sequence at full carrier precision —
"as if the entire application had been run in full precision up to that
point". After each truncated op we measure the elementwise deviation with the
hybrid symmetric metric

    |low - shadow| / max(|shadow|, |low|, _ABS_FLOOR)

which degrades to an absolute-error comparison (in units of ``_ABS_FLOOR``)
when the shadow value is zero or denormal — a raw ``|low-shadow|/|shadow|``
would divide by zero there and poison the per-location max with ``inf``/
``nan``. The metric is bounded by 2 for finite lanes; ``inf`` is reserved for
genuine lane disagreement on finiteness (one lane overflowed or went NaN).
Elements above the user threshold are *flagged* and accumulated per source
location. The result is the paper's heatmap of code locations that do not
react well to truncation.

Unlike RAPTOR's pointer-swizzling shadow structs (shared-memory only, crashes
on MPI reductions), the report is a pure pytree of counters that rides the
normal SPMD data path — mem-mode here works under jit, scan, cond, while and
across meshes.

Trajectory mode (``traj_len > 0``, see ``repro.profile.trajectory``) widens
the accumulators to ``(traj_len, n_loc)`` ring buffers indexed by a step
counter that advances once per iteration of every OUTERMOST loop (the app's
``step`` scan / solver ``while``), so the report records *when* each site's
error appears, not just how large it got. The step counter and the ring
buffers ride the same functional carry as the scalar stats — never a Python
closure — so all iterations of scan/while/cond bodies are reflected.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax._src import core as jcore
from jax._src import source_info_util

from repro.core.interpreter import rebinding_scope
from repro.core.policy import TruncationPolicy, join_stack
from repro.kernels.quantize_em.ops import quantize

# Hybrid deviation floor: below this magnitude (on BOTH lanes) deviations are
# measured absolutely in units of the floor instead of relatively, so an
# exactly-zero or denormal shadow value can never manufacture an inf/nan
# "relative" error (the zero-crossing poisoning bug).
_ABS_FLOOR = 1e-6


def deviation(lowf, shf):
    """Elementwise hybrid symmetric deviation between the truncated and
    shadow lanes (both float32): bounded by 2 for finite inputs, exactly 0
    for bitwise-equal lanes (including inf==inf), and inf only when the
    lanes disagree on finiteness or the shadow itself is NaN."""
    diff = jnp.abs(lowf - shf)
    denom = jnp.maximum(jnp.maximum(jnp.abs(shf), jnp.abs(lowf)),
                        jnp.float32(_ABS_FLOOR))
    rel = diff / denom
    rel = jnp.where(lowf == shf, jnp.zeros_like(rel), rel)
    # inf-vs-finite gives inf/inf = nan, nan in either lane propagates:
    # both are maximal disagreement, not missing data
    return jnp.where(jnp.isnan(rel), jnp.full_like(rel, jnp.inf), rel)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class RaptorReport:
    """Per-location numerical deviation statistics (a pytree of arrays)."""

    locations: Tuple[str, ...] = dataclasses.field(
        metadata=dict(static=True))       # static: loc id -> description
    flags: Any = None                     # i64[n_loc] elements over threshold
    max_rel: Any = None                   # f32[n_loc] max relative deviation
    op_counts: Any = None                 # i64[n_loc] truncated elements seen

    def top(self, k: int = 10) -> List[Tuple[str, int, float]]:
        flags = jax.device_get(self.flags)
        max_rel = jax.device_get(self.max_rel)
        order = sorted(range(len(self.locations)), key=lambda i: -int(flags[i]))
        return [(self.locations[i], int(flags[i]), float(max_rel[i]))
                for i in order[:k]]

    def summary(self, k: int = 10) -> str:
        lines = [f"  {'flags':>12} {'max_rel_err':>12}  location"]
        for loc, f, m in self.top(k):
            lines.append(f"  {f:>12d} {m:>12.3e}  {loc}")
        return "\n".join(lines)

    # ---- cross-shard reductions (SPMD mem-mode) ---------------------------
    # Exactness contract under data parallelism: ``flags`` and ``op_counts``
    # are sums of per-element predicates, so the global report is the
    # elementwise SUM of per-shard reports; ``max_rel`` is a MAX. Reducing
    # per-shard reports with either method below therefore reproduces the
    # single-device report bit-for-bit (integer sums are exact; float max is
    # order-invariant). Note the jit/GSPMD path (``memtrace(mesh=...)``)
    # needs NO explicit reduction — XLA already emits the cross-device
    # collectives for the in-graph sums/maxes.

    def allreduce(self, axis_name: str) -> "RaptorReport":
        """In-SPMD reduction for per-shard reports built INSIDE a
        ``shard_map``/``pmap`` body: ``psum`` of flags/op_counts, ``pmax``
        of max_rel over the mapped mesh axis.

        A shard_map body computes per-SHARD semantics, so the reduced
        report equals the global one exactly when each shard's execution is
        a slice of the global program (per-example models, contractions
        along unsharded dims). Programs with cross-batch reductions (a
        global mean/loss) should use ``memtrace(mesh=...)`` instead, where
        GSPMD keeps the reduction — and hence the report — global."""
        return RaptorReport(
            self.locations,
            lax.psum(self.flags, axis_name),
            lax.pmax(self.max_rel, axis_name),
            lax.psum(self.op_counts, axis_name))

    def merge(self, other: "RaptorReport") -> "RaptorReport":
        """Host-side pairwise reduction (e.g. across processes/ranks, or the
        ticks of a serving run). The result holds numpy arrays with the
        counts in int64: a sum of many reports passes 2^31, where the
        device's int32 would wrap."""
        if self.locations != other.locations:
            raise ValueError("RaptorReport.merge: location tables differ "
                             "(reports come from different computations)")
        return RaptorReport(
            self.locations,
            np.asarray(self.flags, np.int64)
            + np.asarray(other.flags, np.int64),
            np.maximum(np.asarray(self.max_rel), np.asarray(other.max_rel)),
            np.asarray(self.op_counts, np.int64)
            + np.asarray(other.op_counts, np.int64))

    @staticmethod
    def merge_all(reports: Sequence["RaptorReport"]) -> "RaptorReport":
        if not reports:
            raise ValueError("merge_all needs at least one report")
        out = reports[0]
        for r in reports[1:]:
            out = out.merge(r)
        return out


def _tree_flags():
    return jax.tree_util.tree_structure((0, 0, 0))


class _Recorder:
    """Mutable-during-trace location table; emits functional accumulators.

    ``traj_len > 0`` switches the stats carry into trajectory mode: the
    tuple grows ring buffers plus a step counter (see module docstring).
    ``traj_sites`` (substring patterns over location descriptions) narrows
    which locations get trajectory columns — blamed/selected sites only —
    shrinking the per-step carry; unselected sites keep their whole-run
    totals and simply have no temporal row."""

    def __init__(self, threshold: float, traj_len: int = 0, traj_sites=None):
        self.threshold = threshold
        self.traj_len = int(traj_len)
        self.traj_sites = (tuple(traj_sites) if traj_sites is not None
                           else None)
        self.locations: List[str] = []
        self.loc_index: Dict[str, int] = {}
        self.traj_cols: Dict[int, int] = {}
        self.n_traj = 1

    def loc_id(self, desc: str) -> int:
        if desc not in self.loc_index:
            self.loc_index[desc] = len(self.locations)
            self.locations.append(desc)
        return self.loc_index[desc]

    def freeze_traj_cols(self) -> None:
        """Assign trajectory columns once the location table is complete."""
        if self.traj_sites is None:
            self.traj_cols = {i: i for i in range(len(self.locations))}
        else:
            self.traj_cols = {}
            for i, desc in enumerate(self.locations):
                if any(pat in desc for pat in self.traj_sites):
                    self.traj_cols[i] = len(self.traj_cols)
        self.n_traj = max(len(self.traj_cols), 1)

    def traj_col(self, idx: int):
        return self.traj_cols.get(idx)


def _count_dtype():
    return jnp.int64 if jax.config.jax_enable_x64 else jnp.int32


def _zero_stats(n: int, traj_len: int = 0, n_traj=None):
    cdt = _count_dtype()
    base = (jnp.zeros((n,), cdt),
            jnp.zeros((n,), jnp.float32),
            jnp.zeros((n,), cdt))
    if not traj_len:
        return base
    nt = n if n_traj is None else n_traj
    return base + (jnp.zeros((traj_len, nt), jnp.float32),  # ring: max dev
                   jnp.zeros((traj_len, nt), jnp.float32),  # ring: |err| sum
                   jnp.zeros((traj_len, nt), jnp.float32),  # ring: |shadow| sum
                   jnp.zeros((traj_len, nt), cdt),          # ring: elements
                   jnp.zeros((nt,), jnp.float32),           # step row: max dev
                   jnp.zeros((nt,), jnp.float32),           # step row: |err|
                   jnp.zeros((nt,), jnp.float32),           # step row: |shadow|
                   jnp.zeros((nt,), cdt),                   # step row: elements
                   jnp.zeros((), jnp.int32))                # step counter


def _accumulate(stats, idx: int, low, shadow, threshold: float, tcol=None):
    flags, max_rel, op_counts, *traj = stats
    lowf = low.astype(jnp.float32)
    shf = shadow.astype(jnp.float32)
    rel = deviation(lowf, shf)
    n_flag = jnp.sum(rel > threshold).astype(flags.dtype)
    m = (jnp.max(rel) if rel.size else jnp.float32(0)).astype(jnp.float32)
    flags = flags.at[idx].add(n_flag)
    max_rel = max_rel.at[idx].max(m)
    op_counts = op_counts.at[idx].add(jnp.asarray(low.size, op_counts.dtype))
    if not traj:
        return (flags, max_rel, op_counts)
    if tcol is None:
        return (flags, max_rel, op_counts, *traj)
    (t_max, t_abs, t_mag, t_cnt,
     r_max, r_abs, r_mag, r_cnt, step) = traj
    # Per-op writes touch only the small (n_traj,) current-step row at a
    # STATIC column (a cheap size-1 update, not a dynamic-index scatter on
    # the (traj_len, n_traj) ring); the ring buffers are written once per
    # step by _fold_step_row. This is what keeps trajectory mode close to
    # plain memtrace cost.
    aerr = jnp.abs(lowf - shf)
    aerr = jnp.where(lowf == shf, jnp.zeros_like(aerr), aerr)
    aerr = jnp.where(jnp.isnan(aerr), jnp.full_like(aerr, jnp.inf), aerr)
    err_sum = (jnp.sum(aerr) if rel.size else jnp.float32(0))
    mag_sum = (jnp.sum(jnp.abs(shf)) if rel.size else jnp.float32(0))
    r_max = r_max.at[tcol].max(m)
    r_abs = r_abs.at[tcol].add(err_sum.astype(jnp.float32))
    r_mag = r_mag.at[tcol].add(mag_sum.astype(jnp.float32))
    r_cnt = r_cnt.at[tcol].add(jnp.asarray(low.size, r_cnt.dtype))
    return (flags, max_rel, op_counts, t_max, t_abs, t_mag, t_cnt,
            r_max, r_abs, r_mag, r_cnt, step)


def _fold_step_row(stats):
    """Fold the current-step row accumulators into the ring buffers at
    ``step % traj_len`` and clear them. Values land in the same rows the
    old per-op ring writes used (max-of-maxes / sum-of-sums, and untouched
    columns fold max(.., 0)/+0 — exact no-ops on the non-negative stats),
    so the report is unchanged; only the write traffic moves."""
    if len(stats) == 3:
        return stats
    (flags, max_rel, op_counts, t_max, t_abs, t_mag, t_cnt,
     r_max, r_abs, r_mag, r_cnt, step) = stats
    row = jnp.remainder(step, t_max.shape[0])
    t_max = t_max.at[row].max(r_max)
    t_abs = t_abs.at[row].add(r_abs)
    t_mag = t_mag.at[row].add(r_mag)
    t_cnt = t_cnt.at[row].add(r_cnt)
    return (flags, max_rel, op_counts, t_max, t_abs, t_mag, t_cnt,
            jnp.zeros_like(r_max), jnp.zeros_like(r_abs),
            jnp.zeros_like(r_mag), jnp.zeros_like(r_cnt), step)


def _bump_step(stats):
    """Advance the trajectory step counter (end of one outermost-loop
    iteration), folding the finished step's row into the ring first;
    identity for non-trajectory stats."""
    if len(stats) == 3:
        return stats
    stats = _fold_step_row(stats)
    return stats[:-1] + (stats[-1] + jnp.int32(1),)


def shadowed_callable(closed: jcore.ClosedJaxpr, out_tree,
                      policy: TruncationPolicy, threshold: float,
                      impl: str = "auto", *, flat_shardings=None,
                      traj_len: int = 0, traj_sites=None):
    """jit-close the paired (truncated, shadow) evaluation once — the
    mem-mode analogue of ``interpreter.quantized_callable``. The RaptorReport
    rides out of jit as a pytree (static location table, array stats).

    ``flat_shardings`` (pre-resolved per-leaf, see ``distributed.sharding.
    flatten_arg_shardings``) GSPMD-partition the paired evaluation over the
    mesh; the report's in-graph sums/maxes become global collectives so it
    is exact under data parallelism (see ``RaptorReport`` reduction
    notes). ``traj_len > 0`` returns a ``TrajectoryReport`` instead (per-step
    ring buffers, same exactness contract)."""
    from repro.core.interpreter import _jit_sharded

    def run(flat):
        outs, report = eval_shadowed(closed.jaxpr, closed.consts, list(flat),
                                     policy, threshold, impl,
                                     traj_len=traj_len, traj_sites=traj_sites)
        return jax.tree_util.tree_unflatten(out_tree, outs), report

    return _jit_sharded(run, flat_shardings)


def eval_shadowed(jaxpr: jcore.Jaxpr, consts: Sequence[Any], args: Sequence[Any],
                  policy: TruncationPolicy, threshold: float, impl: str = "auto",
                  *, traj_len: int = 0,
                  traj_sites=None) -> Tuple[List[Any], Any]:
    """Two-pass evaluation: first a dry trace to build the static location
    table (so the stats arrays have a fixed shape), then the paired eval.

    Returns ``(outs, RaptorReport)``; with ``traj_len > 0`` the report is a
    :class:`repro.profile.trajectory.TrajectoryReport` whose ring buffers
    hold one row per outermost-loop iteration (modulo ``traj_len``).
    ``traj_sites`` (substring patterns over location descriptions) narrows
    the trajectory columns to the matching locations."""
    rec = _Recorder(threshold, traj_len, traj_sites)
    _collect_locations(jaxpr, policy, rec, "")
    n = max(len(rec.locations), 1)
    if not rec.locations:
        rec.loc_id("<no truncated locations>")
    rec.freeze_traj_cols()

    stats = _zero_stats(n, traj_len, rec.n_traj if traj_len else None)
    outs, _, stats = _eval(jaxpr, consts, args, args, policy, threshold, impl,
                           rec, stats)
    # residual fold: ops after (or outside) the outermost loops accumulated
    # into the current-step row since the last bump — land them in the ring
    stats = _fold_step_row(stats)
    report = RaptorReport(tuple(rec.locations), stats[0], stats[1], stats[2])
    if traj_len:
        from repro.profile.trajectory import TrajectoryReport, scope_of_location
        cols = sorted(rec.traj_cols, key=rec.traj_cols.get)
        report = TrajectoryReport(
            totals=report,
            scopes=tuple(scope_of_location(rec.locations[i]) for i in cols),
            max_rel=stats[3], abs_sum=stats[4], mag_sum=stats[5],
            op_counts=stats[6], steps_seen=stats[-1],
            columns=tuple(cols))
    return outs, report


def _loc_desc(eqn, prefix: str) -> str:
    ns = str(eqn.source_info.name_stack)
    frame = source_info_util.user_frame(eqn.source_info.traceback)
    src = f"{frame.file_name.split('/')[-1]}:{frame.start_line}" if frame else "?"
    scope = f"{prefix}/{ns}" if prefix and ns else (prefix or ns or "<root>")
    return f"{scope} {eqn.primitive.name} @ {src}"


_SUB_JAXPRS = {
    "jit": ("jaxpr",), "pjit": ("jaxpr",), "closed_call": ("call_jaxpr",),
    "remat2": ("jaxpr",), "checkpoint": ("jaxpr",),
    "scan": ("jaxpr",), "while": ("cond_jaxpr", "body_jaxpr"),
    "custom_jvp_call": ("call_jaxpr",), "custom_vjp_call": ("call_jaxpr",),
}


def _collect_locations(jaxpr: jcore.Jaxpr, policy, rec: _Recorder, prefix: str):
    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        sub_prefix = join_stack(prefix, str(eqn.source_info.name_stack))
        if prim in _SUB_JAXPRS:
            for key in _SUB_JAXPRS[prim]:
                inner = eqn.params[key]
                inner = inner.jaxpr if isinstance(inner, jcore.ClosedJaxpr) else inner
                _collect_locations(inner, policy, rec, sub_prefix)
            continue
        if prim == "cond":
            for br in eqn.params["branches"]:
                _collect_locations(br.jaxpr, policy, rec, sub_prefix)
            continue
        for var in eqn.outvars:
            aval = var.aval
            if not hasattr(aval, "dtype"):
                continue
            rule = policy.rule_for(sub_prefix, prim, aval.dtype)
            if rule is not None and jnp.issubdtype(aval.dtype, jnp.floating):
                rec.loc_id(_loc_desc(eqn, prefix))
                break


def _eval(jaxpr, consts, low_args, shadow_args, policy, threshold, impl,
          rec: _Recorder, stats, prefix: str = "", depth: int = 0):
    """``depth`` counts enclosing scan/while bodies: iterations of depth-0
    loops are the trajectory "steps" (the app's outermost step loop); inner
    solver loops accumulate into their enclosing step's row."""
    low_env, sh_env = {}, {}

    def read(v):
        if isinstance(v, jcore.Literal):
            return v.val, v.val
        return low_env[v], sh_env[v]

    def write(v, lo, sh):
        low_env[v] = lo
        sh_env[v] = sh

    for v, c in zip(jaxpr.constvars, consts):
        write(v, c, c)
    for v, lo, sh in zip(jaxpr.invars, low_args, shadow_args):
        write(v, lo, sh)

    for eqn in jaxpr.eqns:
        pairs = [read(v) for v in eqn.invars]
        lows = [p[0] for p in pairs]
        shadows = [p[1] for p in pairs]
        prim = eqn.primitive
        ns = join_stack(prefix, str(eqn.source_info.name_stack))
        with rebinding_scope(eqn):
            handler = _MEM_HOPS.get(prim.name)
            if handler is not None:
                louts, shouts, stats = handler(eqn, lows, shadows, policy,
                                               threshold, impl, rec, stats, ns,
                                               depth)
            else:
                louts = prim.bind(*lows, **eqn.params)
                shouts = prim.bind(*shadows, **eqn.params)
                if not prim.multiple_results:
                    louts, shouts = [louts], [shouts]
                louts, shouts = list(louts), list(shouts)
                for i, var in enumerate(eqn.outvars):
                    aval = var.aval
                    if not hasattr(aval, "dtype"):
                        continue
                    rule = policy.rule_for(ns, prim.name, aval.dtype)
                    if rule is not None and jnp.issubdtype(aval.dtype, jnp.floating):
                        q = quantize(louts[i], rule.fmt, impl=impl)
                        if rule.mask is not None:
                            q = jnp.where(rule.mask(louts[i]), q, louts[i])
                        louts[i] = q
                        idx = rec.loc_id(_loc_desc(eqn, prefix))
                        stats = _accumulate(stats, idx, q, shouts[i], threshold,
                                            rec.traj_col(idx))
        for var, lo, sh in zip(eqn.outvars, louts, shouts):
            write(var, lo, sh)

    lo_outs = [read(v)[0] for v in jaxpr.outvars]
    sh_outs = [read(v)[1] for v in jaxpr.outvars]
    return lo_outs, sh_outs, stats


# ---- mem-mode HOP handlers (stats ride the carry) --------------------------
# The stats tuple is ALWAYS threaded through the functional carry of the
# rebuilt HOP — never captured from the enclosing Python closure — so every
# iteration of scan/while (and whichever cond branch runs) contributes to the
# per-site accumulators; an error that only appears at iteration k>1 is
# recorded exactly like one at iteration 0 (pinned by tests/test_memmode.py).

def _mem_call(eqn, lows, shadows, policy, threshold, impl, rec, stats,
              prefix="", depth=0):
    closed = eqn.params.get("call_jaxpr", eqn.params.get("jaxpr"))
    closed = closed if isinstance(closed, jcore.ClosedJaxpr) else jcore.ClosedJaxpr(closed, ())
    outs, shouts, stats = _eval(closed.jaxpr, closed.consts, lows, shadows,
                                policy, threshold, impl, rec, stats, prefix,
                                depth)
    return outs, shouts, stats


def _mem_scan(eqn, lows, shadows, policy, threshold, impl, rec, stats,
              prefix="", depth=0):
    p = eqn.params
    closed = p["jaxpr"]
    nc, ncarry = p["num_consts"], p["num_carry"]
    lo_c, sh_c = lows[:nc], shadows[:nc]
    lo_carry, sh_carry = tuple(lows[nc:nc + ncarry]), tuple(shadows[nc:nc + ncarry])
    lo_xs, sh_xs = tuple(lows[nc + ncarry:]), tuple(shadows[nc + ncarry:])

    def body(carry, xs):
        lo_car, sh_car, st = carry
        lo_x, sh_x = xs
        env_low = list(lo_c) + list(lo_car) + list(lo_x)
        env_sh = list(sh_c) + list(sh_car) + list(sh_x)
        lo_out, sh_out, st2 = _eval(closed.jaxpr, closed.consts, env_low,
                                    env_sh, policy, threshold, impl, rec, st,
                                    prefix, depth + 1)
        if depth == 0:
            st2 = _bump_step(st2)   # one outermost scan trip = one step
        lo_out = tuple(lo_out)
        sh_out = tuple(sh_out)
        return ((lo_out[:ncarry], sh_out[:ncarry], st2),
                (lo_out[ncarry:], sh_out[ncarry:]))

    (lo_fin, sh_fin, stats), (lo_ys, sh_ys) = lax.scan(
        body, (lo_carry, sh_carry, stats), (lo_xs, sh_xs),
        length=p["length"], reverse=p["reverse"], unroll=p["unroll"])
    return list(lo_fin) + list(lo_ys), list(sh_fin) + list(sh_ys), stats


def _mem_while(eqn, lows, shadows, policy, threshold, impl, rec, stats,
               prefix="", depth=0):
    p = eqn.params
    cond_closed = _as_closed(p["cond_jaxpr"])
    body_closed = _as_closed(p["body_jaxpr"])
    cn, bn = p["cond_nconsts"], p["body_nconsts"]
    lo_cc, sh_cc = lows[:cn], shadows[:cn]
    lo_bc, sh_bc = lows[cn:cn + bn], shadows[cn:cn + bn]
    lo_car, sh_car = tuple(lows[cn + bn:]), tuple(shadows[cn + bn:])

    def cond_fn(carry):
        lo_c, sh_c, st = carry
        # the truncated program decides control flow; the shadow lane rides
        # along the same path (RAPTOR runs ONE binary — shadows are values,
        # not an alternate execution). Stats from cond-body ops are dropped:
        # a predicate can't update the carry.
        lo, _, _ = _eval(cond_closed.jaxpr, cond_closed.consts,
                         list(lo_cc) + list(lo_c), list(sh_cc) + list(sh_c),
                         policy, threshold, impl, rec, st, prefix, depth + 1)
        return lo[0]

    def body_fn(carry):
        lo_c, sh_c, st = carry
        lo, sh, st2 = _eval(body_closed.jaxpr, body_closed.consts,
                            list(lo_bc) + list(lo_c),
                            list(sh_bc) + list(sh_c),
                            policy, threshold, impl, rec, st, prefix,
                            depth + 1)
        if depth == 0:
            st2 = _bump_step(st2)   # one outermost while trip = one step
        return tuple(lo), tuple(sh), st2

    lo_fin, sh_fin, stats = lax.while_loop(
        cond_fn, body_fn, (lo_car, sh_car, stats))
    return list(lo_fin), list(sh_fin), stats


def _mem_cond(eqn, lows, shadows, policy, threshold, impl, rec, stats,
              prefix="", depth=0):
    idx, *lo_ops = lows
    _, *sh_ops = shadows

    def make_branch(br):
        closed = _as_closed(br)

        def branch(ops):
            lo_in, sh_in, st = ops
            lo, sh, st2 = _eval(closed.jaxpr, closed.consts, list(lo_in),
                                list(sh_in), policy, threshold, impl, rec,
                                st, prefix, depth)
            return tuple(lo), tuple(sh), st2

        return branch

    lo_outs, sh_outs, stats = lax.switch(
        idx, [make_branch(b) for b in eqn.params["branches"]],
        (tuple(lo_ops), tuple(sh_ops), stats))
    return list(lo_outs), list(sh_outs), stats


def _as_closed(jx):
    return jx if isinstance(jx, jcore.ClosedJaxpr) else jcore.ClosedJaxpr(jx, ())


_MEM_HOPS = {
    "jit": _mem_call, "pjit": _mem_call, "closed_call": _mem_call,
    "remat2": _mem_call, "checkpoint": _mem_call,
    "custom_jvp_call": _mem_call, "custom_vjp_call": _mem_call,
    "scan": _mem_scan,
    "while": _mem_while,
    "cond": _mem_cond,
}
