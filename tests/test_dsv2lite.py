"""DeepSeek-V2-Lite as the program serves it, held to a plain reference.

The reference below is a straightforward float32 ``jax.numpy`` forward of
the architecture, written from DeepSeek-V2's published modelling code and
run at matmul precision ``highest``: latent attention in its expanded form
(q one plain projection, YaRN RoPE on the rope half, the softmax scale
times mscale^2), the dense lead layer, and the MoE layers' softmax routing
over every expert with the held share of the routed experts plus the
shared ones. It has no cache, no kernels and no batching, and reads the
program's parameter tree, nothing else of the program. The tests run the
smoke configuration (float32) on the CPU.
"""
import dataclasses
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import MoEConfig, YarnScaling, get_config
from repro.models import Model, attention, common, moe
from repro.serving import Engine


# --------------------------------------------------------------------------
# the plain reference
# --------------------------------------------------------------------------

def ref_yarn(cfg):
    """(inverse frequencies, cos/sin factor, softmax scale) by the
    published formulas, in float64."""
    m, sc = cfg.mla, cfg.rope_scaling
    dim, base = m.rope_head_dim, cfg.rope_theta
    extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    scale = (m.nope_head_dim + m.rope_head_dim) ** -0.5
    if sc is None:
        return extra, 1.0, scale

    def corr(rot):
        return dim * math.log(sc.original_max_position_embeddings
                              / (rot * 2 * math.pi)) / (2 * math.log(base))
    low = max(math.floor(corr(sc.beta_fast)), 0)
    high = min(math.ceil(corr(sc.beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    inv = extra / sc.factor * ramp + extra * (1 - ramp)

    def ms(s):
        return 0.1 * s * math.log(sc.factor) + 1.0
    return inv, ms(sc.mscale) / ms(sc.mscale_all_dim), \
        scale * ms(sc.mscale_all_dim) ** 2


def _norm(x, s, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * s


def _swiglu(x, wi, wo):
    a = x @ wi
    g, u = jnp.split(a, 2, axis=-1)
    return (g * jax.nn.sigmoid(g) * u) @ wo


def ref_moe(p, x, mc):
    """The held experts' routed part plus the shared experts, token by
    token, with no capacity."""
    probs = jax.nn.softmax(x @ p["router"], axis=-1)
    gates, ids = lax.top_k(probs, mc.top_k)
    if mc.renormalize:
        gates = gates / jnp.sum(gates, -1, keepdims=True)
    gates = gates * mc.routed_scaling_factor
    y = jnp.zeros_like(x)
    for j, e in enumerate(mc.held):
        g = jnp.sum(jnp.where(ids == e, gates, 0.0), -1)
        y = y + g[:, None] * _swiglu(x, p["wi"][j], p["wo"][j])
    if mc.n_shared:
        y = y + _swiglu(x, p["shared_wi"], p["shared_wo"])
    return y


def ref_forward(params, tokens, cfg):
    """Logits (T, vocab) of one sequence."""
    params = jax.tree_util.tree_map(lambda t: t.astype(jnp.float32), params)
    m, H, eps = cfg.mla, cfg.n_heads, cfg.norm_eps
    dn, dr, dv = m.nope_head_dim, m.rope_head_dim, m.v_head_dim
    inv, cs, scale = ref_yarn(cfg)
    T = tokens.shape[0]
    ang = np.arange(T)[:, None, None] * inv
    sin, cos = jnp.asarray(np.sin(ang) * cs), jnp.asarray(np.cos(ang) * cs)

    def rope(x):
        x1, x2 = x[..., :dr // 2], x[..., dr // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

    def attn(p, x):
        q = (x @ p["q_proj"]).reshape(T, H, dn + dr)
        kv = x @ p["kv_down"]
        c = _norm(kv[:, :m.kv_lora], p["kv_norm"], eps)
        k_pe = rope(kv[:, None, m.kv_lora:])
        up = (c @ p["kv_up"]).reshape(T, H, dn + dv)
        k = jnp.concatenate([up[..., :dn], jnp.broadcast_to(
            k_pe, (T, H, dr))], -1)
        qq = jnp.concatenate([q[..., :dn], rope(q[..., dn:])], -1)
        s = jnp.einsum("qhd,khd->hqk", qq, k) * scale
        s = jnp.where(np.tril(np.ones((T, T), bool)), s, -jnp.inf)
        o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), up[..., dn:])
        return o.reshape(T, H * dv) @ p["wo"]

    def block(p, x, ffn):
        x = x + attn(p["attn"], _norm(x, p["norm1"]["scale"], eps))
        return x + ffn(_norm(x, p["norm2"]["scale"], eps))

    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens]
        for p in params["lead_layers"]:
            x = block(p, x, lambda h, p=p: _swiglu(h, p["mlp"]["wi"],
                                                    p["mlp"]["wo"]))
        n = params["layers"]["norm1"]["scale"].shape[0]
        for i in range(n):
            p = jax.tree_util.tree_map(lambda t: t[i], params["layers"])
            x = block(p, x, lambda h, p=p: ref_moe(p["moe"], h, cfg.moe))
        x = _norm(x, params["final_norm"]["scale"], eps)
        return x @ params["lm_head"]


# --------------------------------------------------------------------------
# configuration, YaRN, q without LoRA
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def smoke():
    cfg = get_config("deepseek-v2-lite", "smoke")
    # a chip's share of the routed experts, so that the share is served
    cfg = cfg.replace(moe=dataclasses.replace(cfg.moe,
                                              held_experts=(0, 1, 2, 3)))
    model = Model(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(0))


def test_published_config():
    cfg = get_config("deepseek-v2-lite")
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.vocab) == \
        (27, 2048, 16, 102400)
    assert cfg.mla.q_lora is None and cfg.mla.kv_lora == 512
    mc = cfg.moe
    assert (mc.n_experts, mc.top_k, mc.d_expert, mc.n_shared) == \
        (64, 6, 1408, 2)
    assert mc.held == tuple(range(64)) and not mc.renormalize
    assert (mc.first_k_dense, mc.d_ff_dense) == (1, 10944)
    n = Model(cfg).n_params()
    # the published 15.7B: embedding + head 419.4M, attention 13.77M a
    # layer, the dense FFN 67.2M, each MoE layer's 64 routed + 2 shared
    # experts and router 571.1M, the norms 0.1M
    assert n == (2 * 102400 * 2048 + 27 * 13_762_560 + 3 * 2048 * 10944
                 + 26 * (2048 * 64 + 66 * 3 * 2048 * 1408)
                 + 27 * (2 * 2048 + 512) + 2048) == 15_706_484_224
    # one EP8 rank holds 8 of the 64 routed experts: 6.22 GB in bf16
    share = Model(cfg.replace(moe=dataclasses.replace(
        mc, held_experts=tuple(range(8))))).n_params()
    assert n - share == 26 * 56 * 3 * 2048 * 1408
    assert 2 * share == 6_221_978_624


def test_yarn_frequencies_and_scale_by_hand():
    """V2-Lite's YaRN: the ramp runs from pair 10 to pair 23 of 32 (the
    pairs turning 32 and 1 times over the 4096 original positions); below
    it the original frequency, above it the frequency over 40; cos and sin
    are not scaled (mscale == mscale_all_dim); the softmax scale is
    192^-0.5 (0.1 * 0.707 * ln 40 + 1)^2."""
    cfg = get_config("deepseek-v2-lite")
    sc = cfg.rope_scaling
    assert common.yarn_correction_range(sc, 64, 1e4) == (10, 23)
    inv = np.asarray(common.rope_freqs(64, 1e4, sc))
    base = 1e4 ** (-np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(inv[:11], base[:11], rtol=1e-6)
    np.testing.assert_allclose(inv[23:], base[23:] / 40, rtol=1e-6)
    r = (15 - 10) / 13
    np.testing.assert_allclose(inv[15], base[15] * (1 - r) + base[15] / 40 * r,
                               rtol=1e-6)
    assert common.rope_scale(sc) == 1.0
    mscale = 0.1 * 0.707 * math.log(40) + 1
    assert mscale == pytest.approx(1.2608, abs=1e-4)
    assert attention.mla_softmax_scale(cfg) == pytest.approx(
        192 ** -0.5 * 1.5896, rel=1e-4)
    # no scaling: the plain frequencies and scale
    assert attention.mla_softmax_scale(cfg.replace(rope_scaling=None)) == \
        pytest.approx(192 ** -0.5)
    # mscale != mscale_all_dim scales cos and sin by their ratio
    other = YarnScaling(factor=40, original_max_position_embeddings=4096,
                        mscale=1.0, mscale_all_dim=0.707)
    assert common.rope_scale(other) == pytest.approx(
        (0.1 * math.log(40) + 1) / mscale)


def test_q_without_lora(smoke):
    """``q_lora=None``: one plain projection d -> H x 192, no down
    projection, norm or up projection."""
    cfg, model, params = smoke
    p = params["lead_layers"][0]["attn"]
    m = cfg.mla
    assert set(p) == {"q_proj", "kv_down", "kv_norm", "kv_up", "wo"}
    assert p["q_proj"].shape == (cfg.d_model, cfg.n_heads * (
        m.nope_head_dim + m.rope_head_dim))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 5, cfg.d_model))
    pos = jnp.broadcast_to(jnp.arange(5)[None], (2, 5))
    q_nope, q_rope = attention._mla_q(p, x, cfg, pos)
    q = (x @ p["q_proj"]).reshape(2, 5, cfg.n_heads, -1).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(q_nope, q[..., :m.nope_head_dim], rtol=1e-6)
    # with q LoRA the three matrices are there instead
    lora = cfg.replace(mla=m.__class__(q_lora=8, kv_lora=m.kv_lora,
                                       rope_head_dim=m.rope_head_dim,
                                       nope_head_dim=m.nope_head_dim,
                                       v_head_dim=m.v_head_dim))
    assert {"q_down", "q_norm", "q_up"} <= set(
        attention.mla_param_defs(lora)) and "q_proj" not in \
        attention.mla_param_defs(lora)


# --------------------------------------------------------------------------
# the program against the reference
# --------------------------------------------------------------------------

def test_expanded_forward_matches_reference(smoke):
    """The program's training/prefill forward (expanded MLA, grouped
    experts at 2 x 80 tokens) against the reference, per sequence. Both
    compute in float32; the bound is float32 summation order over a few
    layers."""
    cfg, model, params = smoke
    toks = jax.random.randint(jax.random.PRNGKey(2), (2, 80), 0, cfg.vocab)
    got = jax.jit(model.forward)(params, {"tokens": toks})
    for b in range(2):
        want = ref_forward(params, toks[b], cfg)
        np.testing.assert_allclose(got[b], want, rtol=0, atol=2e-5)


def test_decode_through_the_cache_matches_reference(smoke):
    """Prefill token by token, then decode, through the latent cache
    written in place, slots at different cursors: every position's logits
    against the reference's full forward over the same tokens. The bound
    is float32 rounding of the absorbed against the expanded form."""
    cfg, model, params = smoke
    B, T = 3, 10
    toks = np.asarray(jax.random.randint(jax.random.PRNGKey(3), (B, T), 0,
                                         cfg.vocab))
    starts = [0, 2, 5]              # each slot joins at its own tick
    cache = model.init_cache(B, T + 6)
    step = jax.jit(model.decode_step)
    got = [[] for _ in range(B)]
    for tick in range(T + max(starts)):
        feed = np.zeros(B, np.int32)
        for b in range(B):
            i = tick - starts[b]
            feed[b] = toks[b, i] if 0 <= i < T else 0
        if tick in starts:
            b = starts.index(tick)
            cache = dict(cache, pos=cache["pos"].at[b].set(0))
        logits, cache = step(params, cache, jnp.asarray(feed))
        for b in range(B):
            if 0 <= tick - starts[b] < T:
                got[b].append(np.asarray(logits[b]))
    for b in range(B):
        want = ref_forward(params, jnp.asarray(toks[b]), cfg)
        np.testing.assert_allclose(np.stack(got[b]), want, rtol=0, atol=2e-5)


def test_engine_serves_what_the_reference_puts_first(smoke):
    """Requests through ``Engine`` (continuous batching, more requests than
    slots, prefill then decode): at every served position the served
    token's reference logit is the reference's best, up to float32
    rounding, which is all that can separate them in float32."""
    cfg, model, params = smoke
    rng = np.random.RandomState(4)
    work = [(rng.randint(1, cfg.vocab, n).astype(np.int32), m)
            for n, m in [(3, 6), (7, 4), (2, 8), (5, 5)]]
    eng = Engine(model, params, batch_size=2, max_seq_len=24)
    handles = [eng.submit(p, max_new_tokens=m) for p, m in work]
    eng.run()
    eng.assert_zero_recompile()
    for h, (prompt, m) in zip(handles, work):
        assert h.status == "ok" and len(h.out_tokens) == m
        seq = np.concatenate([prompt, h.out_tokens[:-1]])
        ref = np.asarray(ref_forward(params, jnp.asarray(seq), cfg))
        rows = ref[len(prompt) - 1:]
        gap = rows.max(-1) - rows[np.arange(m), h.out_tokens]
        assert gap.max() < 1e-4, gap


def test_engine_counts_experts_and_latent_cache(smoke):
    """The dispatch span's static counts: the routed experts each MoE layer
    holds, and the latent cache's bytes (every layer's c_kv and k_rope)."""
    cfg, model, params = smoke
    eng = Engine(model, params, batch_size=2, max_seq_len=24)
    m = cfg.mla
    assert eng._counters == {
        "experts_held": 4,
        "latent_cache_bytes": cfg.n_layers * 2 * 24
        * (m.kv_lora + m.rope_head_dim) * 4}


# --------------------------------------------------------------------------
# the expert share
# --------------------------------------------------------------------------

def _share_cfg(held, n_shared=2):
    return get_config("deepseek-v2-lite", "smoke").replace(
        moe=MoEConfig(n_experts=8, top_k=3, d_expert=16, n_shared=n_shared,
                      first_k_dense=1, d_ff_dense=32, renormalize=False,
                      routed_scaling_factor=1.5, held_experts=held))


def _layer_params(cfg, key):
    from repro.models.common import init_tree
    return init_tree(moe.moe_param_defs(cfg), key, jnp.float32)


@pytest.mark.parametrize("tokens", [16, 300], ids=["dense", "grouped"])
def test_four_shares_sum_to_the_whole_layer(tokens):
    """Four disjoint shares of two experts each: their routed parts, with
    the shared experts counted once, add up to the reference's whole
    layer (all eight experts), at decode size (every held expert over
    every token) and above it (grouped over the routed pairs)."""
    whole_cfg = _share_cfg(None)
    p = _layer_params(whole_cfg, jax.random.PRNGKey(5))
    x = jax.random.normal(jax.random.PRNGKey(6), (1, tokens, 64))
    shared = _swiglu(x[0], p["shared_wi"], p["shared_wo"])
    total = shared
    for i in range(4):
        held = (2 * i, 2 * i + 1)
        cfg = _share_cfg(held)
        p_i = dict(p, wi=p["wi"][2 * i:2 * i + 2], wo=p["wo"][2 * i:2 * i + 2])
        y = jax.jit(lambda p, x, c=cfg: moe.moe_forward(p, x, c))(p_i, x)[0]
        total = total + (y - shared)
    with jax.default_matmul_precision("highest"):
        want = ref_moe(p, x[0], whole_cfg.moe)
    np.testing.assert_allclose(total, want, rtol=0, atol=2e-5)


@pytest.mark.parametrize("tokens", [16, 256], ids=["dense", "grouped"])
def test_no_token_dropped_when_all_route_to_one_expert(tokens):
    """Every token's first choice is expert 0: each still gets its full
    routed part (a capacity of 1.25 x the mean load would have dropped
    most of them)."""
    cfg = _share_cfg((0, 1, 2, 3), n_shared=0)
    p = _layer_params(cfg, jax.random.PRNGKey(7))
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(8), (1, tokens, 64)))
    p["router"] = p["router"].at[:, 0].set(1.0)
    ids, _ = moe.route(p["router"], x[0], cfg.moe)
    assert bool(jnp.all(ids[:, 0] == 0))
    got = jax.jit(lambda p, x: moe.moe_forward(p, x, cfg))(p, x)[0]
    with jax.default_matmul_precision("highest"):
        want = ref_moe(p, x[0], cfg.moe)
    assert float(jnp.min(jnp.abs(want).sum(-1))) > 0
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-5)
