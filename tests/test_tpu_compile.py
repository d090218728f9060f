"""The main path's Pallas kernels compile for TPU v5e at real widths.

These compiles run the TPU compiler against a described v5e topology, so
they need no chip: they catch what interpret mode cannot (Mosaic lowering
rules, VMEM limits, block tiling). Each asserts that the kernel is in the
compiled program. The topology is described inside a module-scoped fixture
(never at import): only one process at a time may load the TPU library, and
a fixture keeps every test worker collecting the same tests.
"""
import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.quantize_em.kernel import (
    LANES, quantize_2d, quantize_2d_dynamic)
from repro.kernels.rwkv6.kernel import wkv6_pallas

KERNEL = "tpu_custom_call"


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def spec(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)


def _compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def test_quantize_2d_static(spec):
    x = spec((4096, LANES), jnp.float32)
    text = _compiled_text(
        lambda x: quantize_2d(x, exp_bits=5, man_bits=7), x)
    assert KERNEL in text


def test_quantize_2d_dynamic(spec):
    x = spec((4096, LANES), jnp.float32)
    row = spec((4,), jnp.int32)
    assert KERNEL in _compiled_text(quantize_2d_dynamic, x, row)


@pytest.mark.parametrize("fused", [False, True], ids=["plain", "epilogue"])
def test_flash_attention_danube(spec, fused):
    """h2o-danube-1.8b: GQA 32/8, head_dim 80, sliding window 4096."""
    q = spec((1, 32, 4096, 80), jnp.bfloat16)
    kv = spec((1, 8, 4096, 80), jnp.bfloat16)
    if fused:
        text = _compiled_text(
            lambda q, k, v, f: flash_attention_pallas(q, k, v, window=4096,
                                                      out_fmt=f),
            q, kv, kv, spec((4,), jnp.int32))
    else:
        text = _compiled_text(
            lambda q, k, v: flash_attention_pallas(q, k, v, window=4096),
            q, kv, kv)
    assert KERNEL in text


def test_wkv6_epilogue_rwkv6_7b(spec):
    """rwkv6-7b: 64 heads of size 64."""
    a = spec((1, 64, 4096, 64), jnp.float32)
    text = _compiled_text(
        lambda r, k, v, w, u, s0, f: wkv6_pallas(r, k, v, w, u, s0,
                                                 out_fmt=f),
        a, a, a, a, spec((64, 64), jnp.float32),
        spec((1, 64, 64, 64), jnp.float32), spec((4,), jnp.int32))
    assert KERNEL in text


@pytest.mark.parametrize("kernel", [True, False],
                         ids=["kv_write", "update_slices"])
def test_danube_decode_writes_kv_cache_in_place(spec, monkeypatch, kernel):
    """h2o-danube-1.8b's decode step at serving width (four of its layers,
    a cache too large for on-chip memory; 16 slots of 2048): with the
    cache donated, as the engine donates it, the compiled step hands the
    cache back in its own buffers, and no instruction copies, relayouts,
    selects over or scatters into the stacked cache. Its writer is the
    ``kv_write`` kernel on TPU (asked for here: this host's backend is the
    CPU), else one update slice a slot."""
    import re

    import numpy as np

    from repro.configs.base import get_config
    from repro.core import TruncationPolicy
    from repro.core.api import truncate
    from repro.models import Model, attention

    monkeypatch.setattr(attention, "_on_tpu", lambda: kernel)
    cfg = get_config("h2o-danube-1.8b").replace(n_layers=4)
    model = Model(cfg)
    B, S = 16, 2048

    def shapes(tree):
        return jax.tree_util.tree_map(lambda t: spec(t.shape, t.dtype), tree)

    params = shapes(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    cache = shapes(jax.eval_shape(lambda: model.init_cache(B, S)))
    step = truncate(model.decode_step,
                    TruncationPolicy.scoped("**/mlp", "e5m7"), impl="pallas")
    compiled = jax.jit(lambda p, c, t: step(p, c, t),
                       donate_argnums=(1,)).lower(
        params, cache, spec((B,), jnp.int32)).compile()
    cache_bytes = sum(int(np.prod(t.shape)) * t.dtype.itemsize
                      for t in jax.tree_util.tree_leaves(cache))
    assert compiled.memory_analysis().alias_size_in_bytes >= cache_bytes
    ops = set()
    for line in compiled.as_text().splitlines():
        m = re.match(r"\s*(?:ROOT )?%\S+ = bf16\[4,16,8,(2048,80|80,2048)\]"
                     r"\S* ([\w-]+)\(", line)
        if m:
            ops.add(m.group(2))
    if kernel:
        # the kernel's outputs, as bitcasts of the cache's own buffers
        assert ops == {"parameter", "bitcast", "get-tuple-element"}, ops
    else:
        assert ops == {"parameter", "get-tuple-element",
                       "dynamic-update-slice"}, ops


def _shapes(spec, tree):
    return jax.tree_util.tree_map(lambda t: spec(t.shape, t.dtype), tree)


def test_kernel_keeps_its_name_under_a_policy(spec, monkeypatch):
    """Under a truncation policy the interpreter rebinds the ``kv_write``
    kernel in its own scope, so the compiled program names it
    ``kv_write`` (not after the enclosing jit), as it does without one."""
    from repro.configs.base import get_config
    from repro.core import TruncationPolicy
    from repro.core.api import truncate
    from repro.models import Model, attention

    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    cfg = get_config("h2o-danube-1.8b", "smoke").replace(
        n_layers=2, dtype="bfloat16", sliding_window=4096)
    model = Model(cfg)
    params = _shapes(spec, jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    cache = _shapes(spec, jax.eval_shape(lambda: model.init_cache(4, 256)))
    step = truncate(model.decode_step,
                    TruncationPolicy.scoped("**/mlp", "e5m7"), impl="pallas")
    text = jax.jit(lambda p, c, t: step(p, c, t), donate_argnums=(1,)).lower(
        params, cache, spec((4,), jnp.int32)).compile().as_text()
    kernels = [line.split(" = ")[0].strip() for line in text.splitlines()
               if KERNEL in line and " = " in line]
    assert any(k.startswith("%kv_write") for k in kernels), kernels
    assert any(k.startswith("%quantize_em") for k in kernels), kernels
    assert not any(k.startswith("%_lambda_") for k in kernels), kernels


def test_dsv2lite_decode_writes_latent_cache_in_place(spec):
    """DeepSeek-V2-Lite's decode step at serving width (the dense layer
    and two MoE layers; 64 slots of 3072) under the cell's policy: with
    the cache donated, the compiled step hands the latent cache back in
    its own buffers, holds no temporary as large as one layer's latent
    cache, and keeps the MLA and MoE scopes in its ops' metadata."""
    import re

    import numpy as np

    from repro.configs.base import get_config
    from repro.core import TruncationPolicy
    from repro.core.api import truncate
    from repro.models import Model

    import dataclasses
    cfg = get_config("deepseek-v2-lite").replace(n_layers=3)
    # one EP8 rank's share of the routed experts, as the chip serves it
    cfg = cfg.replace(moe=dataclasses.replace(
        cfg.moe, held_experts=tuple(range(8))))
    model = Model(cfg)
    B, S = 64, 3072
    params = _shapes(spec, jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    cache = _shapes(spec, jax.eval_shape(lambda: model.init_cache(B, S)))
    step = truncate(model.decode_step,
                    TruncationPolicy.scoped("**/mlp", "e5m7"), impl="pallas")
    compiled = jax.jit(lambda p, c, t: step(p, c, t),
                       donate_argnums=(1,)).lower(
        params, cache, spec((B,), jnp.int32)).compile()
    cache_bytes = sum(int(np.prod(t.shape)) * t.dtype.itemsize
                      for t in jax.tree_util.tree_leaves(cache))
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= cache_bytes
    layer_bytes = B * S * (512 + 64) * 2
    assert mem.temp_size_in_bytes < layer_bytes // 8, mem.temp_size_in_bytes
    text = compiled.as_text()
    names = set(re.findall(r'op_name="[^"]*?/((?:mla|moe)/\w+)', text))
    assert names >= {"mla/absorb", "mla/attend", "mla/write", "moe/router",
                     "moe/experts", "moe/shared"}, names
