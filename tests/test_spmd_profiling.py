"""Mesh-parallel profiling (@spmd tier): sharded truncate_sweep /
mem-mode / autosearch must be bit-for-bit consistent with the single-device
path, and the sharded ladder must keep the O(1)-compile contract while
putting >1 effective probe per dispatch on every device.

Each test runs in a subprocess that forces
``XLA_FLAGS=--xla_force_host_platform_device_count=8``, so the suite passes
on any host; CI's `spmd` job additionally sets the flag at the job level.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

pytestmark = pytest.mark.spmd


def _run_subproc(code: str, timeout: int = 600) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    res = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=timeout)
    assert res.returncode == 0, res.stderr[-4000:]
    line = [l for l in res.stdout.splitlines() if l.startswith("RESULT")][0]
    return json.loads(line[len("RESULT"):])


_PRELUDE = textwrap.dedent("""
    import json
    import numpy as np
    import jax, jax.numpy as jnp
    from jax import lax
    from repro.core import (truncate, truncate_sweep, memtrace,
                            TruncationPolicy, scope)
    from repro.launch.mesh import make_probe_mesh, make_profile_mesh
    from repro.distributed.sharding import batch_sharding
    from repro import search

    assert len(jax.devices()) == 8, jax.devices()

    def _toy(w1, w2, x):
        with scope("attn"):
            h = jnp.tanh(x @ w1)
        with scope("mlp"):
            def body(c, _):
                return jax.nn.relu(c @ w2), None
            h, _ = lax.scan(body, h, None, length=3)
        with scope("head"):
            return jnp.mean(h * h)

    r = np.random.RandomState(0)
    args = (jnp.asarray(r.randn(32, 64) / 8, jnp.float32),
            jnp.asarray(r.randn(64, 64) / 8, jnp.float32),
            jnp.asarray(r.randn(16, 32), jnp.float32))
""")


def test_sharded_sweep_bit_for_bit_2x4_mesh():
    """truncate_sweep on a (probe=2, data=4) mesh: every ladder width's
    output must equal the single-device path bit-for-bit, including a K not
    divisible by the probe axis (identity-padded, sliced back)."""
    out = _run_subproc(_PRELUDE + textwrap.dedent("""
        mesh = make_profile_mesh(2, 4)
        site = TruncationPolicy.everywhere("e5m2")
        pols = [TruncationPolicy.everywhere(f"e8m{m}")
                for m in (15, 10, 7, 5, 3, 2)]
        h0 = truncate_sweep(_toy, site)(*args)
        h1 = truncate_sweep(_toy, site, mesh=mesh)(*args)
        t6 = h0.tables(pols)
        eq6 = bool(np.array_equal(jax.device_get(h0.batch(t6)),
                                  jax.device_get(h1.batch(t6))))
        t5 = h0.tables(pols[:5])   # K=5: not divisible by probe axis (2)
        b5 = jax.device_get(h1.batch(t5))
        eq5 = bool(np.array_equal(jax.device_get(h0.batch(t5)), b5))
        singles = [float(h0(h0.table(p))) for p in pols[:5]]
        print("RESULT" + json.dumps({
            "eq6": eq6, "eq5": eq5, "k5": list(np.shape(b5)),
            "singles_match": bool(np.allclose(singles, b5, rtol=0, atol=0)),
        }))
    """))
    assert out["eq6"], "sharded ladder diverged from single-device"
    assert out["eq5"], "identity-padded sharded ladder diverged"
    assert out["k5"] == [5], "padding leaked into the output batch"
    assert out["singles_match"]


def test_raptor_report_reductions_2x4_mesh():
    """Mem-mode exactness under data parallelism — the thing RAPTOR cannot
    do (§6.3): (1) GSPMD path: memtrace with the batch sharded 4-way must
    reproduce the single-device report bit-for-bit — including the
    cross-shard mean, which XLA lowers to a global collective; (2)
    shard_map path: per-shard reports of a per-example program reduced with
    RaptorReport.allreduce (psum/pmax) must match the global report (a
    shard_map body computes per-SHARD semantics, so this contract is for
    programs whose sharded execution is a slice of the global one — batch
    reductions belong on the GSPMD path); (3) host-side merge doubles
    counts and keeps maxes."""
    out = _run_subproc(_PRELUDE + textwrap.dedent("""
        from jax.experimental.shard_map import shard_map
        from jax.sharding import PartitionSpec as P

        mesh = make_profile_mesh(2, 4)
        pol = TruncationPolicy.everywhere("e5m2")
        out0, rep0 = memtrace(_toy, pol)(*args)
        sh = [None, None, batch_sharding(mesh, "data")]
        out1, rep1 = memtrace(_toy, pol, mesh=mesh, in_shardings=sh)(*args)

        def eqs(a, b):
            return bool(np.array_equal(jax.device_get(a), jax.device_get(b)))

        # shard_map lane: a PER-EXAMPLE program (no cross-batch reduction,
        # so each shard's execution is exactly its slice of the global
        # program); each shard runs mem-mode on its batch slice (the
        # memtrace wrapper falls back to inline interpretation under the
        # outer trace), then allreduces the report over the data axis
        def _toy_ew(w1, w2, x):
            with scope("attn"):
                h = jnp.tanh(x @ w1)
            with scope("mlp"):
                def bd(c, _):
                    return jax.nn.relu(c @ w2), None
                h, _ = lax.scan(bd, h, None, length=3)
            with scope("head"):
                return h * h

        _, rep_ew = memtrace(_toy_ew, pol)(*args)

        def body(w1, w2, xs):
            _, rep = memtrace(_toy_ew, pol)(w1, w2, xs)
            return rep.allreduce("data")

        rep2 = shard_map(
            body, mesh=mesh,
            in_specs=(P(), P(), P("data")),
            out_specs=P(), check_rep=False)(*args)

        merged = rep0.merge(rep1)
        print("RESULT" + json.dumps({
            "gspmd_flags": eqs(rep0.flags, rep1.flags),
            "gspmd_max_rel": eqs(rep0.max_rel, rep1.max_rel),
            "gspmd_ops": eqs(rep0.op_counts, rep1.op_counts),
            "smap_flags": eqs(rep_ew.flags, rep2.flags),
            "smap_max_rel": eqs(rep_ew.max_rel, rep2.max_rel),
            "smap_ops": eqs(rep_ew.op_counts, rep2.op_counts),
            "out_close": bool(np.allclose(float(out0), float(out1),
                                          rtol=1e-6)),
            "merge_flags": eqs(merged.flags,
                               2 * jnp.asarray(rep0.flags)),
            "merge_max": eqs(merged.max_rel, rep0.max_rel),
            "n_loc": len(rep0.locations),
            "any_flags": int(jax.device_get(rep0.flags).sum()) > 0,
        }))
    """))
    for k in ("gspmd_flags", "gspmd_max_rel", "gspmd_ops", "smap_flags",
              "smap_max_rel", "smap_ops", "out_close", "merge_flags",
              "merge_max", "any_flags"):
        assert out[k], (k, out)
    assert out["n_loc"] >= 3


def test_sharded_ladder_single_compile_multi_probe_per_device():
    """Compile-cache contract under sharding: repeated sharded ladder
    dispatches reuse ONE executable, and each dispatch evaluates more than
    one probe per device (K=8 on a 4-device probe mesh -> 2/device)."""
    out = _run_subproc(_PRELUDE + textwrap.dedent("""
        from repro.launch.compilation import count_compiles
        mesh = make_probe_mesh(4)
        site = TruncationPolicy.everywhere("e5m2")
        pols = [TruncationPolicy.everywhere(f"e8m{m}")
                for m in (15, 10, 7, 5, 3, 2, 23, 11)]
        handle = truncate_sweep(_toy, site, mesh=mesh)(*args)
        tables = handle.tables(pols)
        with count_compiles() as n:
            a = jax.device_get(handle.batch(tables))
            b = jax.device_get(handle.batch(handle.tables(pols[::-1])))
        print("RESULT" + json.dumps({
            "compiles": int(n[0]),
            "k": len(pols), "ndev": 4,
            "consistent": bool(np.array_equal(a, b[::-1])),
        }))
    """))
    assert out["compiles"] == 1, f"sharded ladder recompiled: {out}"
    assert out["k"] / out["ndev"] > 1, "fewer than 2 probes per device"
    assert out["consistent"]


def test_trajectory_reduces_exactly_under_mesh():
    """profile_trajectory over a (probe=2, data=4) mesh: every signal the
    temporal analysis decides on — per-step max deviation, op counts, the
    step counter — must equal the single-device trajectory bit-for-bit on
    both the GSPMD path and the shard_map + TrajectoryReport.allreduce
    path. The float SUM buffers (abs_sum/mag_sum) are exact up to
    cross-shard summation order (the usual float-reduction contract), so
    they are pinned to tight allclose instead."""
    out = _run_subproc(_PRELUDE + textwrap.dedent("""
        from jax.experimental.shard_map import shard_map
        from jax.sharding import PartitionSpec as P
        from repro.core import profile_trajectory

        mesh = make_profile_mesh(2, 4)
        pol = TruncationPolicy.everywhere("e5m2")

        def _steps(w1, w2, x):
            def body(c, _):
                with scope("mlp"):
                    c = jnp.tanh(c @ w2)
                return c, None
            h, _ = lax.scan(body, jnp.tanh(x @ w1), None, length=5)
            return h * h          # per-example output (shard_map-exact)

        args2 = args
        out0, t0 = profile_trajectory(_steps, pol, threshold=1e-3, n_steps=6)(*args2)
        sh = [None, None, batch_sharding(mesh, "data")]
        out1, t1 = profile_trajectory(_steps, pol, threshold=1e-3, n_steps=6,
                                      mesh=mesh, in_shardings=sh)(*args2)

        def eqs(a, b):
            return bool(np.array_equal(jax.device_get(a), jax.device_get(b)))

        def body(w1, w2, xs):
            _, t = profile_trajectory(_steps, pol, threshold=1e-3, n_steps=6)(
                w1, w2, xs)
            return t.allreduce("data")

        t2 = shard_map(body, mesh=mesh,
                       in_specs=(P(), P(), P("data")),
                       out_specs=P(), check_rep=False)(*args2)

        def close(a, b):
            return bool(np.allclose(jax.device_get(a), jax.device_get(b),
                                    rtol=1e-5, atol=1e-5))

        print("RESULT" + json.dumps({
            "gspmd": all(eqs(getattr(t0, k), getattr(t1, k))
                         for k in ("max_rel", "op_counts", "steps_seen")),
            "smap": all(eqs(getattr(t0, k), getattr(t2, k))
                        for k in ("max_rel", "op_counts", "steps_seen")),
            "gspmd_sums": (close(t0.abs_sum, t1.abs_sum)
                           and close(t0.mag_sum, t1.mag_sum)),
            "smap_sums": (close(t0.abs_sum, t2.abs_sum)
                          and close(t0.mag_sum, t2.mag_sum)),
            "steps": int(jax.device_get(t0.steps_seen)),
            "any_err": float(np.sum(jax.device_get(t0.abs_sum))) > 0,
            "out_eq": eqs(out0, out1),
        }))
    """))
    assert out["gspmd"], "sharded trajectory diverged from single-device"
    assert out["smap"], "allreduced per-shard trajectories diverged"
    assert out["gspmd_sums"] and out["smap_sums"]
    assert out["steps"] == 5 and out["any_err"] and out["out_eq"]


def test_sharded_autosearch_dispatch_stats_match_unsharded():
    """Identity-padded candidate rows must never leak into accounting:
    with a ladder whose logical width (7) does NOT divide the probe axis
    (8), the sharded search must report bit-identical n_dispatches,
    max_dispatch_rows, evals and history to the unsharded run — padding
    only widens the physical signature (probe_batch)."""
    out = _run_subproc(_PRELUDE + textwrap.dedent("""
        mesh = make_probe_mesh()   # 8 devices; k_logical = 6 + 1 = 7
        kw = dict(threshold=1e-2, budget=48)
        r0 = search.autosearch(_toy, args, search.rel_error, **kw)
        r1 = search.autosearch(_toy, args, search.rel_error, mesh=mesh, **kw)
        a0 = {p: [a.man_bits, a.excluded] for p, a in r0.assignments.items()}
        a1 = {p: [a.man_bits, a.excluded] for p, a in r1.assignments.items()}
        print("RESULT" + json.dumps({
            "same": a0 == a1,
            "evals": [r0.evals_used, r1.evals_used],
            "dispatches": [r0.n_dispatches, r1.n_dispatches],
            "max_rows": [r0.max_dispatch_rows, r1.max_dispatch_rows],
            "history": r0.history == r1.history,
            "k": [r0.probe_batch, r1.probe_batch],
            "ndev": r1.n_devices,
        }))
    """))
    assert out["same"]
    assert out["evals"][0] == out["evals"][1]
    assert out["dispatches"][0] == out["dispatches"][1], out
    assert out["max_rows"][0] == out["max_rows"][1], out
    assert out["history"]
    # the physical batch IS padded (7 -> 8): the contract is that padding
    # never shows up in the derived stats, not that it doesn't exist
    assert out["k"] == [7, 8] and out["ndev"] == 8


def test_autosearch_mesh_matches_single_device_bench_model():
    """Acceptance: autosearch on the bench model over an 8-device host
    probe mesh returns the SAME per-scope assignments as the single-device
    search, within the O(1) compile budget, with >1 effective probe per
    compile-dispatch per device (widths ladder of 10 on 8 shards)."""
    out = _run_subproc(_PRELUDE + textwrap.dedent("""
        import sys, os
        sys.path.insert(0, os.getcwd())
        from repro.launch.compilation import count_compiles
        from benchmarks.common import bench_model, bench_batch

        cfg, model, params = bench_model()
        batch = bench_batch(cfg)
        widths = (23, 15, 12, 10, 8, 7, 6, 5, 3, 2)
        r0 = search.autosearch(model.loss, (params, batch),
                               search.loss_degradation, 48,
                               threshold=5e-3, widths=widths)
        mesh = make_probe_mesh()   # all 8 devices
        with count_compiles() as n:
            r1 = search.autosearch(model.loss, (params, batch),
                                   search.loss_degradation, 48,
                                   threshold=5e-3, widths=widths, mesh=mesh)
        a0 = {p: [a.man_bits, a.excluded] for p, a in r0.assignments.items()}
        a1 = {p: [a.man_bits, a.excluded] for p, a in r1.assignments.items()}
        print("RESULT" + json.dumps({
            "same": a0 == a1, "a0": a0, "a1": a1,
            "compiles": int(n[0]),
            "n_compiles": r1.n_compiles,
            "converged": bool(r0.converged) and bool(r1.converged),
            "evals": [r0.evals_used, r1.evals_used],
            "budget_ok": r1.evals_used <= 48,
            "ppd": r1.probes_per_dispatch_per_device,
            "ndev": r1.n_devices,
        }))
    """), timeout=900)
    assert out["same"], f"assignments diverged: {out['a0']} vs {out['a1']}"
    assert out["converged"]
    assert out["budget_ok"] and out["evals"][0] == out["evals"][1]
    assert out["compiles"] <= 2, out
    assert out["n_compiles"] <= 2
    assert out["ndev"] == 8
    assert out["ppd"] > 1, ("sharded ladder must batch >1 probe per device "
                            f"per dispatch, got {out['ppd']}")
