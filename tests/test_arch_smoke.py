"""Per-assigned-architecture smoke tests (deliverable f).

Each arch instantiates its REDUCED config (same family/topology, small
dims), runs one forward/train step on CPU, asserts output shapes and no
NaNs, and checks decode parity: token-by-token decode logits must match the
full parallel forward (catches cache/rope/state bugs — the strongest cheap
correctness signal for sequence models).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs.base import get_config, ARCH_IDS
from repro.models import Model

B, S = 2, 32


def make_batch(cfg, rng):
    batch = {
        "tokens": jnp.asarray(rng.integers(0, cfg.vocab, (B, S)), jnp.int32),
        "labels": jnp.asarray(rng.integers(0, cfg.vocab, (B, S)), jnp.int32),
    }
    if cfg.input_mode == "embeds":
        batch["embeds"] = jnp.asarray(
            rng.standard_normal((B, S, cfg.d_model)), jnp.float32)
        if cfg.rope_type == "mrope":
            batch["positions"] = jnp.broadcast_to(
                jnp.arange(S, dtype=jnp.int32)[None, None], (3, B, S))
        batch.pop("tokens")
    if cfg.family == "encdec":
        batch["src_embeds"] = jnp.asarray(
            rng.standard_normal((B, S, cfg.d_model)), jnp.float32)
    return batch


# heavyweight archs run in the slow (full/CI) tier; the default tier-1 run
# keeps one dense and one MoE representative (see pytest.ini)
_FAST_ARCHS = {"h2o-danube-1.8b", "olmoe-1b-7b", "deepseek-v2-lite"}
_ARCH_PARAMS = [
    a if a in _FAST_ARCHS else pytest.param(a, marks=pytest.mark.slow)
    for a in ARCH_IDS
]


@pytest.mark.parametrize("arch_id", _ARCH_PARAMS)
def test_smoke_forward_and_train_step(arch_id):
    cfg = get_config(arch_id, "smoke")
    model = Model(cfg)
    rng = np.random.default_rng(0)
    params = model.init(jax.random.PRNGKey(0))
    batch = make_batch(cfg, rng)

    logits = jax.jit(model.forward)(params, batch)
    assert logits.shape == (B, S, cfg.vocab)
    assert bool(jnp.all(jnp.isfinite(logits)))

    loss, grads = jax.jit(jax.value_and_grad(model.loss))(params, batch)
    assert bool(jnp.isfinite(loss))
    gn = jax.tree_util.tree_reduce(
        lambda a, g: a + jnp.sum(jnp.square(g.astype(jnp.float32))), grads, 0.0)
    assert bool(jnp.isfinite(gn)) and float(gn) > 0


@pytest.mark.parametrize("arch_id", _ARCH_PARAMS)
def test_smoke_decode_parity(arch_id):
    """Greedy decode logits at each position == parallel forward logits."""
    cfg = get_config(arch_id, "smoke")
    model = Model(cfg)
    rng = np.random.default_rng(1)
    params = model.init(jax.random.PRNGKey(1))
    T = 8
    batch = make_batch(cfg, rng)
    if cfg.input_mode == "embeds":
        small = {"embeds": batch["embeds"][:, :T]}
        if "positions" in batch:
            small["positions"] = batch["positions"][:, :, :T]
    elif cfg.family == "encdec":
        small = {"src_embeds": batch["src_embeds"][:, :T],
                 "tokens": batch["tokens"][:, :T]}
    else:
        small = {"tokens": batch["tokens"][:, :T]}
    full_logits = jax.jit(model.forward)(params, small)  # (B, T, V)

    cache = model.init_cache(B, T + 1)
    if cfg.family == "encdec":
        # cross-kv must be populated from the encoder for parity
        from repro.models import encdec as ed
        memory = ed.encode(params, small["src_embeds"], cfg)
        hd = cfg.resolved_head_dim
        ck, cv = [], []
        for i in range(cfg.n_layers):
            p_l = jax.tree_util.tree_map(lambda t: t[i],
                                         params["dec_layers"])
            k = (memory @ p_l["cross_attn"]["wk"]).reshape(
                B, T, cfg.n_kv_heads, hd).transpose(0, 2, 1, 3)
            v = (memory @ p_l["cross_attn"]["wv"]).reshape(
                B, T, cfg.n_kv_heads, hd).transpose(0, 2, 1, 3)
            ck.append(k)
            cv.append(v)
        cache = ed.init_cache(cfg, B, T + 1, memory_len=T)
        cache["cross_k"] = jnp.stack(ck)
        cache["cross_v"] = jnp.stack(cv)

    step = jax.jit(model.decode_step)
    maxdiff = 0.0
    for t in range(T):
        if cfg.input_mode == "embeds":
            logits, cache = step(params, cache,
                                 jnp.zeros((B,), jnp.int32),
                                 embeds=small["embeds"][:, t:t + 1])
        else:
            logits, cache = step(params, cache, small["tokens"][:, t])
        maxdiff = max(maxdiff,
                      float(jnp.max(jnp.abs(logits - full_logits[:, t]))))
    assert maxdiff < 2e-2, f"{arch_id}: decode/parallel mismatch {maxdiff}"


def test_ring_cache_wraps_correctly():
    """Decode past the sliding window: ring cache (window-sized) must match
    a full-length cache with window masking, token by token."""
    cfg = get_config("h2o-danube-1.8b", "smoke").replace(sliding_window=4)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(2))
    rng = np.random.default_rng(3)
    T = 12  # 3x the window
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (B, T)), jnp.int32)

    # reference: full parallel forward (window masks inside flash)
    full_logits = jax.jit(model.forward)(params, {"tokens": toks})

    cache = model.init_cache(B, T + 1)
    # ring allocated: cache seq dim == window
    assert cache["layers"]["k"].shape[3] == 4
    step = jax.jit(model.decode_step)
    maxdiff = 0.0
    for t in range(T):
        logits, cache = step(params, cache, toks[:, t])
        maxdiff = max(maxdiff,
                      float(jnp.max(jnp.abs(logits - full_logits[:, t]))))
    assert maxdiff < 2e-2, maxdiff


def test_hymba_ring_plus_global_caches():
    """Hymba: ring caches for SWA layers, full caches for global layers."""
    cfg = get_config("hymba-1.5b", "smoke").replace(sliding_window=4)
    model = Model(cfg)
    cache = model.init_cache(2, 17)
    assert cache["layers"]["kv"]["k"].shape[3] == 4      # ring (SWA)
    assert cache["global"][0]["kv"]["k"].shape[2] == 17  # full (global)
    params = model.init(jax.random.PRNGKey(4))
    rng = np.random.default_rng(5)
    T = 10
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (B, T)), jnp.int32)
    full_logits = jax.jit(model.forward)(params, {"tokens": toks})
    step = jax.jit(model.decode_step)
    cache = model.init_cache(B, T + 1)
    maxdiff = 0.0
    for t in range(T):
        logits, cache = step(params, cache, toks[:, t])
        maxdiff = max(maxdiff,
                      float(jnp.max(jnp.abs(logits - full_logits[:, t]))))
    assert maxdiff < 2e-2, maxdiff
