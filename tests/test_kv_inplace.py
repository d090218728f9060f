"""The serving KV cache is written in place.

``transformer.decode_step`` reads a GQA or MLA stack's cache inside the
layer scan and writes every layer's new rows after it; ``gqa_decode`` and
``mla_decode`` write one layer's cache the same way. Both are held here to
the algorithm they replaced, kept below as the reference: a one-hot
``where`` that rewrites each layer's whole cache, carried through the scan
as ``xs``/``ys``, and attention over the written cache. Then the engine's
compiled decode step and slot reset alias the donated cache and rewrite
none of it, and set-up holds one cache.
"""
import functools
import math
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ArchConfig, get_config
from repro.core import TruncationPolicy
from repro.kernels import kv_write
from repro.models import Model, attention
from repro.models.transformer import apply_norm, layer_forward
from repro.serving import Engine, ShadowConfig

TICKS = 6


# --------------------------------------------------------------------------
# the reference: the one-hot write over the whole cache
# --------------------------------------------------------------------------

def _onehot_attention(q, k_cache, v_cache, cur, window, ring):
    """Attention over the written cache; ``cur`` counts its valid entries."""
    B, Hq, Dk = q.shape
    Hkv, S = k_cache.shape[1], k_cache.shape[2]
    qg = q.reshape(B, Hkv, Hq // Hkv, Dk).astype(jnp.float32)
    s = jnp.einsum("bhgd,bhkd->bhgk", qg,
                   k_cache.astype(jnp.float32)) / math.sqrt(Dk)
    idx = jnp.arange(S)[None, None, None, :]
    cur = cur[:, None, None, None]
    if ring:
        last = cur - 1
        valid = last - jnp.mod(last - idx, S) >= 0
    else:
        valid = idx < cur
        if window is not None:
            valid &= idx >= cur - window
    p = jax.nn.softmax(jnp.where(valid, s, attention.NEG_INF), axis=-1)
    out = jnp.einsum("bhgk,bhkd->bhgd", p, v_cache.astype(jnp.float32))
    return out.reshape(B, Hq, -1).astype(v_cache.dtype)


def onehot_gqa_decode(p, x1, cache, pos, cfg, *, window=None,
                      positions3=None):
    B = x1.shape[0]
    S = cache["k"].shape[2]
    ring = window is not None and S == window
    pos_b = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    q, k, v = attention._project_qkv(p, x1, cfg, pos_b[:, None])
    slot = jnp.mod(pos_b, S) if ring else pos_b
    onehot = (jnp.arange(S)[None, :] == slot[:, None])[:, None, :, None]
    k_cache = jnp.where(onehot, k.astype(cache["k"].dtype), cache["k"])
    v_cache = jnp.where(onehot, v.astype(cache["v"].dtype), cache["v"])
    o = _onehot_attention(q[:, :, 0], k_cache, v_cache, pos_b + 1,
                          None if ring else window, ring)
    return o.reshape(B, 1, -1) @ p["wo"].astype(x1.dtype), {"k": k_cache,
                                                            "v": v_cache}


def onehot_mla_decode(p, x1, cache, pos, cfg):
    """MLA decode as it was: the new latent rows written one-hot into the
    whole layer cache, then attention over the written cache, its products
    in the cache's dtype with float32 sums, as the program reads it."""
    m = cfg.mla
    B, H = x1.shape[0], cfg.n_heads
    S = cache["c_kv"].shape[1]
    pos_b = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    q_nope, q_rope = attention._mla_q(p, x1, cfg, pos_b[:, None])
    c_new, kr_new = attention._mla_latent(p, x1, cfg, pos_b[:, None])
    onehot = (jnp.arange(S)[None, :] == pos_b[:, None])[..., None]
    c_cache = jnp.where(onehot, c_new.astype(cache["c_kv"].dtype),
                        cache["c_kv"])
    r_cache = jnp.where(onehot, kr_new.astype(cache["k_rope"].dtype),
                        cache["k_rope"])
    w_up = p["kv_up"].reshape(m.kv_lora, H, m.nope_head_dim + m.v_head_dim)
    f32, dt = jnp.float32, c_cache.dtype
    q_lat = jnp.einsum("bhd,lhd->bhl", q_nope[:, :, 0].astype(f32),
                       w_up[..., :m.nope_head_dim].astype(f32)).astype(dt)
    s = (jnp.einsum("bhl,bsl->bhs", q_lat, c_cache,
                    preferred_element_type=f32)
         + jnp.einsum("bhd,bsd->bhs", q_rope[:, :, 0].astype(dt), r_cache,
                      preferred_element_type=f32)
         ) * attention.mla_softmax_scale(cfg)
    s = jnp.where(jnp.arange(S)[None, None, :] <= pos_b[:, None, None], s,
                  attention.NEG_INF)
    pr = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    ctx = jnp.einsum("bhs,bsl->bhl", pr.astype(dt), c_cache,
                     preferred_element_type=f32) / jnp.sum(pr, -1)[..., None]
    o = jnp.einsum("bhl,lhd->bhd", ctx,
                   w_up[..., m.nope_head_dim:].astype(f32))
    out = o.reshape(B, 1, -1).astype(x1.dtype) @ p["wo"].astype(x1.dtype)
    return out, {"c_kv": c_cache, "k_rope": r_cache}


def reference_mla_decode_step(params, cache, tokens, cfg):
    """An MLA stack's decode step as it was: the lead layer and every
    scanned layer rewrite their whole latent cache one-hot."""
    pos = cache["pos"]
    x = params["embed"].astype(jnp.dtype(cfg.dtype))[tokens][:, None]
    lead = []
    for p_l, c_l in zip(params["lead_layers"], cache["lead"]):
        x, st = layer_forward(cfg, p_l, x, None, "dense_lead",
                              mix_state=c_l, decode=True, pos=pos)
        lead.append(st)

    def body(x, xs):
        p_l, c_l = xs
        return layer_forward(cfg, p_l, x, None, "moe", is_global=False,
                             mix_state=c_l, decode=True, pos=pos)

    x, stack = lax.scan(body, x, (params["layers"], cache["layers"]))
    x = apply_norm(params["final_norm"], x, cfg)
    logits = x[:, 0].astype(jnp.float32) @ params["lm_head"].astype(
        jnp.float32)
    return logits, {"pos": pos + 1, "layers": stack, "lead": lead}


def reference_decode_step(params, cache, tokens, cfg):
    """A dense GQA stack's decode step as it was: the cache rides the scan
    as ``xs`` and every layer's rewritten cache comes back as ``ys``."""
    pos = cache["pos"]
    x = params["embed"].astype(jnp.dtype(cfg.dtype))[tokens][:, None]

    def body(x, xs):
        p_l, c_l = xs
        return layer_forward(cfg, p_l, x, None, "dense", is_global=False,
                             mix_state=c_l, decode=True, pos=pos)

    x, stack = lax.scan(body, x, (params["layers"], cache["layers"]))
    x = apply_norm(params["final_norm"], x, cfg)
    logits = x[:, 0].astype(jnp.float32) @ params["lm_head"].astype(
        jnp.float32)
    return logits, {"pos": pos + 1, "layers": stack}


# --------------------------------------------------------------------------
# the cases
# --------------------------------------------------------------------------

def _dense(**kw):
    return get_config("h2o-danube-1.8b", "smoke").replace(
        dtype="bfloat16", **kw)


def _mla(**kw):
    return get_config("deepseek-v2-lite", "smoke").replace(
        dtype="bfloat16", **kw)


def _filled(cache, pos, seed):
    """The cache with every entry random (also past each cursor, where the
    mask must keep it out) and the given per-slot cursors."""
    leaves, tree = jax.tree_util.tree_flatten(cache)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    leaves = [jax.random.normal(k, t.shape, jnp.float32).astype(t.dtype)
              if jnp.issubdtype(t.dtype, jnp.floating) else t
              for k, t in zip(keys, leaves)]
    out = jax.tree_util.tree_unflatten(tree, leaves)
    out["pos"] = jnp.asarray(pos, jnp.int32)
    return out


# name: (config, slots, cache length, cursors at the first tick); the
# "kernel" cases write through the TPU kernel, run here in interpret mode
CASES = {
    # ragged per-slot cursors in a full-length cache (window 16 > 12)
    "ragged": (_dense(), 4, 12, [0, 3, 7, 1]),
    # slot 1 is past the cache end from the start, slot 3 runs past it
    "dead_lane": (_dense(), 4, 12, [2, 12, 20, 9]),
    # the same under a mesh, where the write is a select over the cache
    "dead_lane_under_mesh": (_dense(), 4, 12, [2, 12, 20, 9]),
    # a ring cache (window 4 < 12) decoded across its wrap
    "ring": (_dense(sliding_window=4), 4, 12, [0, 2, 3, 5]),
    # encoder-decoder self-attention through gqa_decode
    "encdec": (get_config("seamless-m4t-large-v2", "smoke").replace(
        dtype="bfloat16"), 2, 12, [1, 4]),
    # hymba: ring caches in the scanned layers, full ones in global layers
    "hymba": (get_config("hymba-1.5b", "smoke").replace(
        dtype="bfloat16", sliding_window=4), 2, 12, [1, 6]),
    # two 128-slot blocks; slot 3 is past the end, slot 2 runs past it
    "kernel_ragged": (_dense(sliding_window=4096), 4, 256,
                      [0, 126, 253, 300]),
    # a ring of one block decoded across its wrap
    "kernel_ring": (_dense(sliding_window=128), 4, 300, [5, 125, 127, 130]),
    # one layer's cache at a time
    "kernel_encdec": (get_config("seamless-m4t-large-v2", "smoke").replace(
        dtype="bfloat16"), 2, 128, [3, 126]),
    # MLA latent cache (lead layer and stack); slot 3 starts at the last
    # position and runs past the end
    "mla_ragged": (_mla(), 4, 12, [0, 3, 7, 11]),
    # slot 1 is past the cache end from the start, slot 3 runs past it
    "mla_dead_lane": (_mla(), 4, 12, [2, 12, 20, 9]),
    # the same under a mesh, where the write is a select over the cache
    "mla_dead_lane_under_mesh": (_mla(), 4, 12, [2, 12, 20, 9]),
}


def _run(step, params, cache, toks):
    logits, caches = [], []
    for t in range(toks.shape[0]):
        lg, cache = step(params, cache, toks[t])
        logits.append(np.asarray(lg))
        caches.append(jax.tree_util.tree_map(np.asarray, cache))
    return logits, caches


@pytest.mark.parametrize("case", list(CASES))
def test_decode_step_matches_onehot_reference(case, monkeypatch):
    """Over several ticks the logits agree to bf16 tolerance and the caches
    agree exactly, in every lane: dead ones too."""
    cfg, B, S, pos = CASES[case]
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(1))
    cache = _filled(model.init_cache(B, S), pos, seed=2)
    if cfg.family == "dense":
        # a ring case's cache is window-sized
        assert cache["layers"]["k"].shape[3] == min(S, cfg.sliding_window)
    toks = jnp.asarray(np.random.default_rng(3).integers(
        0, cfg.vocab, (TICKS, B)), jnp.int32)

    if case.endswith("under_mesh"):
        monkeypatch.setattr(attention, "mesh_active", lambda: True)
    if case.startswith("kernel"):
        monkeypatch.setattr(attention, "_on_tpu", lambda: True)
        monkeypatch.setattr(kv_write, "kv_write", functools.partial(
            kv_write.kv_write, interpret=True))
    got = _run(jax.jit(model.decode_step), params, cache, toks)
    # encdec and hymba keep calling gqa_decode: their reference is their
    # own step with the one-hot write in its place
    monkeypatch.setattr(attention, "gqa_decode", onehot_gqa_decode)
    monkeypatch.setattr(attention, "mla_decode", onehot_mla_decode)
    if cfg.attn_type == "mla":
        ref_step = lambda p, c, t: reference_mla_decode_step(p, c, t, cfg)
    elif cfg.family == "dense":
        ref_step = lambda p, c, t: reference_decode_step(p, c, t, cfg)
    else:
        ref_step = model.decode_step
    want = _run(jax.jit(ref_step), params, cache, toks)

    for t in range(TICKS):
        scale = float(np.max(np.abs(want[0][t])))
        np.testing.assert_allclose(got[0][t], want[0][t], rtol=0,
                                   atol=2 ** -7 * scale)
        for g, w in zip(jax.tree_util.tree_leaves(got[1][t]),
                        jax.tree_util.tree_leaves(want[1][t])):
            np.testing.assert_array_equal(g, w)


def test_gqa_decode_keeps_its_contract():
    """``gqa_decode`` returns the written layer cache: the new rows at each
    lane's slot (pos mod S on a ring), nothing for a cursor past the end,
    every other entry as it was."""
    cfg = _dense()
    p = Model(cfg).init(jax.random.PRNGKey(4))["layers"]["attn"]
    p = jax.tree_util.tree_map(lambda t: t[0], p)
    B, S = 3, 8
    pos = jnp.asarray([2, 9, 5], jnp.int32)
    x1 = jax.random.normal(jax.random.PRNGKey(5), (B, 1, cfg.d_model),
                           jnp.float32).astype(jnp.bfloat16)
    for window, slots in [(None, [2, None, 5]), (S, [2, 1, 5])]:
        cache = _filled(attention.gqa_init_cache(cfg, B, S, jnp.bfloat16),
                        pos, seed=6)
        del cache["pos"]
        _, new = jax.jit(lambda p, x, c: attention.gqa_decode(
            p, x, c, pos, cfg, window=window))(p, x1, cache)
        _, rows = jax.jit(lambda p, x, c: attention.gqa_decode_rows(
            p, x, c, pos, cfg, window=window))(p, x1, cache)
        for n in ("k", "v"):
            want = np.array(cache[n])
            for b, s in enumerate(slots):
                if s is not None:
                    want[b, :, s] = np.asarray(rows[n][b])
            np.testing.assert_array_equal(np.asarray(new[n]), want)


# --------------------------------------------------------------------------
# the engine: donation, in place, and the lane reset
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def served():
    cfg = ArchConfig(name="inplace", family="dense", n_layers=3, d_model=64,
                     n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128,
                     vocab=64, dtype="bfloat16", sliding_window=4096)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(7))
    return cfg, model, params


def _workload(cfg, n=5, seed=8):
    r = np.random.RandomState(seed)
    return [(r.randint(1, cfg.vocab, L).astype(np.int32), m)
            for L, m in zip([3, 7, 5, 9, 2][:n], [4, 6, 3, 5, 8][:n])]


def test_decode_step_aliases_the_cache(served):
    """The engine's compiled decode step, shadowed step and slot reset take
    the cache as a donated input and give it back in the same buffers, and
    no ``select`` in the optimised decode step is as large as a layer's
    cache."""
    cfg, model, params = served
    eng = Engine(model, params, batch_size=4, max_seq_len=64,
                 policy=TruncationPolicy.scoped("**/mlp", "e5m7"),
                 shadow=ShadowConfig(rate=1.0))
    cache_bytes = sum(t.nbytes for t in jax.tree_util.tree_leaves(eng.cache))
    tok = jnp.zeros((4,), jnp.int32)
    decode = eng._decode.lower(params, eng.cache, tok).compile()
    shadowed = eng._shadow._step.lower(params, eng.cache, tok).compile()
    reset = eng._reset.lower(eng.cache, jnp.int32(0)).compile()
    for compiled in (decode, shadowed, reset):
        assert compiled.memory_analysis().alias_size_in_bytes >= cache_bytes
    # a layer's cache (slots, KV heads, length, head size), or the stack
    lane = re.compile(r"= \w+\[(\d+,)?4,%d,64,%d\]\S* select\(" % (
        cfg.n_kv_heads, cfg.resolved_head_dim))
    assert not [line for line in decode.as_text().splitlines()
                if lane.search(line)]


def test_donated_engine_serves_without_retracing(served):
    """More requests than slots on the scanned stack: admissions reset
    lanes in place, every path stays at one executable, and the tokens are
    those of each request served alone."""
    cfg, model, params = served
    work = _workload(cfg)
    alone = []
    for prompt, m in work:
        one = Engine(model, params, batch_size=1, max_seq_len=32)
        h = one.submit(prompt, max_new_tokens=m)
        one.run()
        alone.append(h.out_tokens)
    eng = Engine(model, params, batch_size=2, max_seq_len=32)
    handles = [eng.submit(p, max_new_tokens=m) for p, m in work]
    eng.run()
    eng.assert_zero_recompile()
    assert eng.cache_sizes()["decode"] == 1
    assert eng.cache_sizes()["reset"] == 1
    assert [h.out_tokens for h in handles] == alone


def test_reset_clears_nan_left_in_a_lane(served):
    """A quarantined request can leave NaN in its lane. The in-place reset
    zeroes the lane on admission, so the next request there serves the
    tokens a clean engine serves (under the mask ``p @ v`` would still
    carry a NaN)."""
    cfg, model, params = served
    (prompt, m), = _workload(cfg, n=1)
    clean = Engine(model, params, batch_size=2, max_seq_len=32)
    want = clean.submit(prompt, max_new_tokens=m)
    clean.run()
    eng = Engine(model, params, batch_size=2, max_seq_len=32)

    def poison(path, t):
        if path[0].key != "layers":
            return t
        return t.at[:, 0].set(jnp.nan)
    eng.cache = jax.tree_util.tree_map_with_path(poison, eng.cache)
    got = eng.submit(prompt, max_new_tokens=m)
    eng.run()
    assert got.status == "ok"
    assert got.out_tokens == want.out_tokens


@pytest.mark.parametrize("arch", ["h2o-danube-1.8b", "deepseek-v2-lite"])
def test_setup_holds_one_cache(arch):
    """After ``Engine(...)`` one buffer of each cache leaf is live: set-up
    warms nothing up through a second cache (under a mesh it warms up
    through a throwaway step that takes the cache donated)."""
    cfg = get_config(arch, "smoke").replace(dtype="bfloat16")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(9))
    B, S = 3, 37                   # shapes no weight has
    eng = Engine(model, params, batch_size=B, max_seq_len=S,
                 policy=TruncationPolicy.scoped("**/mlp", "e5m7"))
    leaves = jax.tree_util.tree_leaves(eng.cache)
    live = [a for a in jax.live_arrays() if not a.is_deleted()]
    for leaf in leaves:
        same = [a for a in live if a.shape == leaf.shape
                and a.dtype == leaf.dtype]
        # lead layers' caches share a shape: one buffer each
        want = sum(t.shape == leaf.shape and t.dtype == leaf.dtype
                   for t in leaves)
        assert len(same) == want, (leaf.shape, len(same))
    eng.submit(np.arange(1, 4, dtype=np.int32), max_new_tokens=2)
    eng.run()
    assert eng.cache_sizes()["decode"] == 1


def test_settle_warms_up_in_place(served):
    """The warm-up that a serving mesh needs takes the cache donated and
    zeroes it in its own buffers' layout."""
    cfg, model, params = served
    eng = Engine(model, params, batch_size=2, max_seq_len=16)
    cache = jax.tree_util.tree_map(lambda t: t + 1, eng.cache)
    out = eng._settle(model.decode_step, params, cache)
    assert all(t.is_deleted() for t in jax.tree_util.tree_leaves(cache))
    for t in jax.tree_util.tree_leaves(out):
        assert not np.any(np.asarray(t))
    assert jax.tree_util.tree_map(lambda t: t.shape, out) == \
        jax.tree_util.tree_map(lambda t: t.shape, eng.cache)
