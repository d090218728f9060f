"""Distribution: logical-axis resolution unit tests + an 8-fake-device
subprocess that executes a sharded train step and a sharded decode step
end-to-end (real multi-device SPMD on CPU)."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

import jax
from jax.sharding import PartitionSpec as P

from repro.distributed import sharding as shd


def mk_mesh(shape, names):
    from repro.launch.mesh import make_mesh
    return make_mesh(shape, names)


def test_resolve_basic():
    mesh = mk_mesh((1, 1), ("data", "model"))
    spec = shd._resolve(mesh, shd.DEFAULT_PARAM_RULES,
                        ("embed", "heads"), (64, 64))
    # axes of size 1 are dropped by the divisibility guard
    assert spec == P()


def test_resolve_divisibility_guard():
    # kv_heads=2 on a 4-way model axis must fall back to cache_seq sharding
    # (AbstractMesh-style stand-in: _resolve only reads mesh.shape)
    import types
    mesh = types.SimpleNamespace(shape={"data": 2, "model": 4})
    spec = shd._resolve(mesh, shd.DEFAULT_ACT_RULES,
                        ("batch", "kv_heads", "cache_seq", None),
                        (8, 2, 64, 4))
    assert spec == P("data", None, "model")
    # divisible kv_heads win the model axis; cache_seq then drops (axis used)
    spec2 = shd._resolve(mesh, shd.DEFAULT_ACT_RULES,
                         ("batch", "kv_heads", "cache_seq", None),
                         (8, 8, 64, 4))
    assert spec2 == P("data", "model")
    # batch=1 (long_500k): batch sharding dropped
    spec3 = shd._resolve(mesh, shd.DEFAULT_ACT_RULES,
                         ("batch", "kv_heads", "cache_seq", None),
                         (1, 2, 64, 4))
    assert spec3 == P(None, None, "model")


def test_constrain_noop_without_mesh():
    import jax.numpy as jnp
    x = jnp.ones((4, 4))
    assert shd.constrain(x, "batch", "embed") is x


def test_probe_sharding_helpers():
    """Single-device-safe checks of the mesh-parallel profiling helpers:
    axis sizing, pad-to-shard-multiple arithmetic, and the replication
    fallback when a mesh lacks the requested axis."""
    import types
    mesh = types.SimpleNamespace(shape={"probe": 4, "data": 2})
    assert shd.probe_axis_size(None) == 1
    assert shd.probe_axis_size(mesh, "probe") == 4
    assert shd.probe_axis_size(mesh, "nope") == 1
    assert shd.pad_to_shards(7, None) == 7
    assert shd.pad_to_shards(0, mesh, "probe") == 0
    assert shd.pad_to_shards(1, mesh, "probe") == 4
    assert shd.pad_to_shards(7, mesh, "probe") == 8
    assert shd.pad_to_shards(8, mesh, "probe") == 8

    real = mk_mesh((1, 1), ("probe", "data"))
    assert shd.probe_sharding(real, "probe").spec == P("probe")
    assert shd.probe_sharding(real, "absent").spec == P()
    assert shd.batch_sharding(real, "data").spec == P("data")
    assert shd.replicated(real).spec == P()


def test_flatten_arg_shardings():
    """Per-argument prefix broadcasting onto the flat (args, kwargs) leaf
    list: one prefix entry covers its whole argument subtree, a single
    sharding broadcasts to positional leaves only, and kwargs leaves ALWAYS
    replicate (a scalar kwarg must never inherit a rank-1 batch spec)."""
    mesh = mk_mesh((1, 1), ("probe", "data"))
    params = {"w1": 1, "w2": 2}        # leaf identity is all that matters
    batch = {"x": 3, "y": 4}

    flat = shd.flatten_arg_shardings(mesh, None, (params, batch), {})
    assert [s.spec for s in flat] == [P()] * 4

    flat = shd.flatten_arg_shardings(
        mesh, [None, shd.batch_sharding(mesh, "data")], (params, batch), {})
    assert [s.spec for s in flat] == [P(), P(), P("data"), P("data")]

    # single sharding: positional leaves sharded, kwargs replicated
    flat = shd.flatten_arg_shardings(
        mesh, P("data"), (params,), {"scale": 5})
    assert [s.spec for s in flat] == [P("data"), P("data"), P()]

    # PartitionSpec entries resolve against the mesh; kwargs replicate
    flat = shd.flatten_arg_shardings(
        mesh, (P("data"), None), (params, batch), {"k": 0})
    assert [s.spec for s in flat] == [P("data"), P("data"), P(), P(), P()]

    assert shd.flatten_arg_shardings(None, None, (params,), {}) is None
    import pytest as _pytest
    with _pytest.raises(ValueError):
        shd.flatten_arg_shardings(mesh, [None, None, None], (params, batch),
                                  {})


SUBPROC = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.configs.base import get_config, SHAPES, InputShape
    from repro.distributed import sharding as shd
    from repro.launch import specs as sp
    from repro.launch.mesh import make_host_mesh
    from repro.models import Model
    from repro.train.trainer import TrainConfig, make_train_step, init_opt_state
    from repro.optim.adamw import AdamWConfig

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((4, 2), ("data", "model"))
    cfg = get_config("olmoe-1b-7b", "smoke").replace(dtype="float32")
    model = Model(cfg)
    out = {}
    with shd.use_mesh(mesh):
        tc = TrainConfig(optimizer=AdamWConfig(lr=1e-2, weight_decay=0.0))
        step_fn = jax.jit(make_train_step(model, tc))
        params = model.init(jax.random.PRNGKey(0))
        # place params according to the FSDP x TP rules
        defs = model.param_defs()
        from repro.models.common import ParamDef
        sh = jax.tree_util.tree_map(
            lambda pd: shd.param_sharding(pd.shape, pd.axes, mesh),
            defs, is_leaf=lambda x: isinstance(x, ParamDef))
        params = jax.tree_util.tree_map(jax.device_put, params, sh)
        opt = init_opt_state(model, params, tc)
        r = np.random.RandomState(0)
        toks = r.randint(0, cfg.vocab, (8, 17))
        batch = {"tokens": jnp.asarray(toks[:, :-1], jnp.int32),
                 "labels": jnp.asarray(toks[:, 1:], jnp.int32)}
        losses = []
        for i in range(5):
            params, opt, m = step_fn(params, opt, batch, jnp.int32(i))
            losses.append(float(m["loss"]))
        out["losses"] = losses
        # sharded param survived: check one TP-sharded tensor
        wi = params["layers"]["moe"]["wi"]
        out["wi_sharded"] = str(wi.sharding.spec)

        # decode under the same mesh
        shape = InputShape("d", 32, 8, "decode")
        cache = model.init_cache(8, 32)
        logits, cache = jax.jit(model.decode_step)(
            params, cache, jnp.zeros((8,), jnp.int32))
        out["decode_finite"] = bool(jnp.all(jnp.isfinite(logits)))
    print("RESULT" + json.dumps(out))
""")


@pytest.mark.slow
def test_multidevice_train_and_decode():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("JAX_PLATFORMS", None)
    env["JAX_PLATFORMS"] = "cpu"
    res = subprocess.run([sys.executable, "-c", SUBPROC], env=env,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-3000:]
    line = [l for l in res.stdout.splitlines() if l.startswith("RESULT")][0]
    out = json.loads(line[len("RESULT"):])
    assert out["losses"][-1] < out["losses"][0]
    assert out["decode_finite"]
    assert "model" in out["wi_sharded"]
