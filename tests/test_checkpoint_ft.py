"""Checkpointing + fault tolerance: atomicity, resume, elastic re-shard,
supervisor retry, data-cursor determinism."""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.checkpoint.checkpointer import Checkpointer
from repro.data.pipeline import DataConfig, Pipeline, Prefetcher
from repro.distributed.fault_tolerance import (
    StragglerMonitor, SupervisorConfig, run_supervised, best_mesh_shape,
)


def tree(seed=0):
    r = np.random.RandomState(seed)
    return {"w": jnp.asarray(r.randn(4, 4), jnp.float32),
            "nested": {"b": jnp.asarray(r.randn(3), jnp.float32),
                       "none": None},
            "step": jnp.int32(7)}


def assert_tree_equal(a, b):
    fa = jax.tree_util.tree_leaves(a)
    fb = jax.tree_util.tree_leaves(b)
    assert len(fa) == len(fb)
    for x, y in zip(fa, fb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_save_restore_roundtrip(tmp_path):
    ck = Checkpointer(str(tmp_path), async_save=False)
    t = tree()
    ck.save(3, t, extra={"data_step": 11})
    out, manifest = ck.restore(t)
    assert_tree_equal(out, t)
    assert manifest["extra"]["data_step"] == 11
    assert ck.latest_step() == 3


def test_async_save_with_wait(tmp_path):
    ck = Checkpointer(str(tmp_path), async_save=True)
    ck.save(1, tree(1))
    ck.save(2, tree(2))
    ck.wait()
    out, _ = ck.restore(tree(2))
    assert_tree_equal(out, tree(2))


def test_keep_k_gc(tmp_path):
    ck = Checkpointer(str(tmp_path), keep_k=2, async_save=False)
    for s in (1, 2, 3, 4):
        ck.save(s, tree(s))
    steps = sorted(d for d in os.listdir(tmp_path) if d.startswith("step_"))
    assert steps == ["step_000000003", "step_000000004"]


def test_latest_pointer_atomic(tmp_path):
    """A stale tmp dir from a 'crashed' save never shadows LATEST."""
    ck = Checkpointer(str(tmp_path), async_save=False)
    ck.save(5, tree(5))
    os.makedirs(tmp_path / ".tmp_step_000000009_zombie", exist_ok=True)
    assert ck.latest_step() == 5
    out, _ = ck.restore(tree(5))
    assert_tree_equal(out, tree(5))


def test_elastic_reshard(tmp_path):
    """Save replicated, restore with explicit shardings on a 1-dev mesh
    (the same code path re-shards onto any elastic mesh shape)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1,), ("data",))
    ck = Checkpointer(str(tmp_path), async_save=False)
    t = {"w": jnp.arange(16, dtype=jnp.float32).reshape(4, 4)}
    ck.save(1, t)
    sh = {"w": NamedSharding(mesh, P("data", None))}
    out, _ = ck.restore(t, shardings=sh)
    assert out["w"].sharding == sh["w"]
    np.testing.assert_array_equal(np.asarray(out["w"]), np.asarray(t["w"]))


def test_best_mesh_shape_elastic():
    assert best_mesh_shape(512, 16) == (32, 16)
    assert best_mesh_shape(256, 16) == (16, 16)
    assert best_mesh_shape(24, 16) == (3, 8)   # degraded pod: fewer chips
    assert best_mesh_shape(7, 16) == (7, 1)


def test_supervisor_restarts_on_failure(tmp_path):
    """step_fn dies twice; supervisor restores and completes the run."""
    state = {"restored": 0, "completed": [], "saved_at": 0}
    failures = {8: True, 13: True}

    def step_fn(step):
        if failures.pop(step, None):
            raise RuntimeError("collective timeout (simulated node death)")
        state["completed"].append(step)

    def save_fn(step):
        state["saved_at"] = step

    def restore_fn():
        state["restored"] += 1
        return state["saved_at"]

    final, restarts, _ = run_supervised(
        step_fn, save_fn, restore_fn, total_steps=20,
        cfg=SupervisorConfig(save_every=5))
    assert final == 20
    assert restarts == 2
    assert state["restored"] == 3  # initial + 2 failures
    assert 20 in [state["saved_at"]]


def test_straggler_monitor():
    m = StragglerMonitor(straggle_factor=2.0)
    for _ in range(10):
        assert not m.record(1.0)
    assert m.record(5.0)      # 5x median flags
    assert not m.record(1.1)


def test_straggler_monitor_rolling_window_eviction():
    """The window evicts oldest samples, so the median tracks the *current*
    regime: after a durable slowdown, old fast samples must age out and the
    new normal must stop alarming."""
    m = StragglerMonitor(straggle_factor=2.0, window=10)
    for _ in range(10):
        m.record(1.0)
    assert len(m._times) == 10
    # regime change: every step is now 3s. The first ones straggle vs the
    # old 1s median...
    assert m.record(3.0)
    # ...but once the window is full of 3s samples, the median has moved
    # and 3s is the new normal
    for _ in range(10):
        m.record(3.0)
    assert len(m._times) == 10          # bounded: evicted, not accumulated
    assert all(t == 3.0 for t in m._times)
    assert not m.record(3.0)
    # and the monitor still alarms relative to the NEW baseline
    assert m.record(7.0)


def test_data_pipeline_resume_determinism():
    cfg = DataConfig(seq_len=16, global_batch=4, vocab=100)
    p1 = Pipeline(cfg)
    batches = [p1.next() for _ in range(5)]
    state = p1.state_dict()
    more1 = [p1.next() for _ in range(3)]
    p2 = Pipeline(cfg)
    p2.load_state_dict(state)
    more2 = [p2.next() for _ in range(3)]
    for a, b in zip(more1, more2):
        np.testing.assert_array_equal(a["tokens"], b["tokens"])
    # and the stream itself is deterministic from step 0
    p3 = Pipeline(cfg)
    np.testing.assert_array_equal(p3.next()["tokens"], batches[0]["tokens"])


def test_prefetcher():
    cfg = DataConfig(seq_len=8, global_batch=2, vocab=50)
    pf = Prefetcher(Pipeline(cfg))
    a = pf.next()
    b = pf.next()
    assert a["tokens"].shape == (2, 8)
    assert not np.array_equal(a["tokens"], b["tokens"])
    pf.close()


def test_memmap_pipeline(tmp_path):
    from repro.data.pipeline import write_token_file
    toks = np.arange(10_000, dtype=np.int32) % 97
    path = str(tmp_path / "tokens.bin")
    write_token_file(path, toks)
    cfg = DataConfig(seq_len=16, global_batch=4, vocab=97, kind="memmap",
                     path=path)
    p = Pipeline(cfg)
    b = p.next()
    assert b["tokens"].shape == (4, 16)
    np.testing.assert_array_equal(b["labels"][:, :-1], b["tokens"][:, 1:])
