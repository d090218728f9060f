"""Bring-up check: RAPTOR-JAX's main path on a TPU, at full model width.

    python chip_smoke.py             # one chip: phases (a)-(d)
    python chip_smoke.py --chips 4   # four chips: candidate-parallel search

Phases, in one process (the chip belongs to one process at a time):

  (a) device   -- JAX must see a TPU; there is no CPU path.
  (b) kernels  -- the Pallas static and runtime-format quantize kernels
                  against the jnp reference on the same device, bit for bit,
                  over >16M elements (several blocks and a partial last one).
  (c) serve    -- h2o-danube-1.8b at its published widths and all 24 layers
                  through ``Engine``: 8 seeded requests, a static policy on
                  the MLP scopes and shadow sampling; one decode executable,
                  shadowed tokens identical to an unshadowed run, and Pallas
                  kernels in the truncated decode step.
  (d) profile  -- ``autosearch`` (runtime-table path: the dynamic kernel)
                  and ``memtrace`` over one full-width danube layer.

``--chips 4`` runs only the search of (d) over a 4-device probe mesh and
again with ``mesh=None``, and checks that both give the same assignments
and eval counts.

Any failure exits non-zero. The last line of stdout is the JSON result,
printed only when every phase passed. Times are host wall clock and
include compilation.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "h2o-danube-1.8b"
SEED = 0
KERNEL_MARKER = "tpu_custom_call"   # how a Pallas kernel shows in TPU HLO
QUANT_ELEMENTS = (1 << 24) + 12345  # >16M: 33 blocks of 512x1024, last partial
STATIC_RUNGS = ("e5m7", "e6m4", "e5m2", "e4m3s")
SERVE_POLICY = "scope:**/mlp=e5m7"
SERVE_BATCH, SERVE_MAX_SEQ = 8, 1024
N_REQUESTS, NEW_TOKENS, PROMPT_LENS = 8, 16, (16, 64)
SHADOW_RATE, SHADOW_SEED = 0.25, 1  # seed 1 samples some, not all, requests
SEARCH_BATCH, SEARCH_SEQ, SEARCH_BUDGET = 2, 512, 16


def log(msg: str) -> None:
    print(msg, flush=True)


def require(cond: bool, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


def phase_device(n_chips: int):
    import jax
    devs = jax.devices()
    d = devs[0]
    log(f"[a] devices: {len(devs)} x {d.platform} ({d.device_kind})")
    require(d.platform == "tpu", f"no TPU: JAX found {d.platform}")
    require(len(devs) >= n_chips,
            f"{n_chips} chips asked for, {len(devs)} present")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def quant_input(key, n: int):
    """Values over 2^-24..2^24 (every rung rounds, over- and underflows;
    no f32 subnormals) with +-0, +-inf and NaN mixed in."""
    import jax
    import jax.numpy as jnp
    k1, k2 = jax.random.split(key)
    mag = jnp.exp2(jax.random.uniform(k1, (n,), jnp.float32, -24.0, 24.0))
    x = mag * jax.random.normal(k2, (n,), jnp.float32)
    specials = jnp.asarray([0.0, -0.0, np.inf, -np.inf, np.nan], jnp.float32)
    return x.at[:specials.size].set(specials)


def phase_kernels(n: int = QUANT_ELEMENTS):
    """Pallas kernels vs the jnp reference, bitwise, on the same device."""
    import jax
    import jax.numpy as jnp
    from repro.core.formats import parse_format
    from repro.kernels.quantize_em.ops import (
        IDENTITY_ROW, format_row, quantize, quantize_dynamic)

    x = quant_input(jax.random.PRNGKey(SEED), n)
    bits = lambda a: np.asarray(jax.lax.bitcast_convert_type(a, jnp.int32))
    x_bits = bits(x)
    rows = [(f, format_row(f)) for f in STATIC_RUNGS] + [
        ("identity", IDENTITY_ROW)]
    out = {}
    for name, row in rows:
        pairs = [("dynamic", lambda a, impl: quantize_dynamic(a, row,
                                                              impl=impl))]
        if name != "identity":
            fmt = parse_format(name)
            pairs.append(("static", lambda a, impl: quantize(a, fmt,
                                                             impl=impl)))
        for kind, fn in pairs:
            kern = jax.jit(lambda a: fn(a, "pallas"))
            text = kern.lower(x).as_text()
            require(KERNEL_MARKER in text,
                    f"{kind} {name}: no Pallas kernel in the program")
            got = bits(kern(x))
            want = bits(jax.jit(lambda a: fn(a, "ref"))(x))
            diff = int(np.count_nonzero(got != want))
            changed = int(np.count_nonzero(got != x_bits))
            log(f"[b] {kind:7s} {name:8s}: {n} elements, {diff} differ "
                f"from ref, {changed} changed by rounding")
            require(diff == 0, f"{kind} {name}: {diff} elements differ")
            require((changed == 0) == (name == "identity"),
                    f"{kind} {name}: rounding changed {changed} elements")
            out[f"{kind}/{name}"] = changed
    return out


def make_requests(vocab: int):
    rng = np.random.RandomState(SEED)
    lo, hi = PROMPT_LENS
    return [rng.randint(1, vocab, int(rng.randint(lo, hi + 1))
                        ).astype(np.int32) for _ in range(N_REQUESTS)]


def serve(model, params, prompts, shadow):
    from repro.serving import Engine
    eng = Engine(model, params, batch_size=SERVE_BATCH,
                 max_seq_len=SERVE_MAX_SEQ, policy=SERVE_POLICY,
                 shadow=shadow)
    handles = [eng.submit(p, max_new_tokens=NEW_TOKENS) for p in prompts]
    eng.run()
    return eng, handles


def phase_serve(cfg):
    """Full-width decode through Engine under a policy, with and without
    shadow sampling."""
    import jax
    import jax.numpy as jnp
    from repro.models import Model
    from repro.serving import ShadowConfig

    model = Model(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(SEED))
    log(f"[c] {cfg.name}: {cfg.n_layers} layers, d_model={cfg.d_model}, "
        f"{model.n_params() / 1e9:.3f}B params ({cfg.dtype})")
    prompts = make_requests(cfg.vocab)

    t0 = time.time()
    eng, handles = serve(model, params, prompts, ShadowConfig(
        rate=SHADOW_RATE, seed=SHADOW_SEED))
    t_shadow = time.time() - t0
    sizes = eng.cache_sizes()
    shadowed = [h.rid for h in handles if h.shadowed]
    log(f"[c] shadowed run: {len(handles)} requests in {t_shadow:.2f}s, "
        f"shadowed rids {shadowed}, executables {sizes}")
    require(all(h.done and h.status == "ok" for h in handles),
            "a request did not finish ok")
    require(all(len(h.out_tokens) == NEW_TOKENS for h in handles),
            "a request stopped short of its token budget")
    require(sizes["decode"] == 1, f"decode executables: {sizes['decode']}")
    require(sizes.get("shadow") == 1, f"shadow executables: {sizes}")
    require(0 < len(shadowed) < len(handles),
            f"shadow sampling took {len(shadowed)} of {len(handles)}")
    report = eng.serving_report
    for loc, flags, err in report.top(3):
        log(f"[c]   shadow site {loc}: flags={flags} max_rel={err:.3e}")

    tok = jnp.zeros((SERVE_BATCH,), jnp.int32)
    text = eng._decode.lower(params, eng.cache, tok).compile().as_text()
    n_kernels = text.count(KERNEL_MARKER)
    log(f"[c] truncated decode step: {n_kernels} {KERNEL_MARKER} ops")
    require(n_kernels > 0, "no Pallas kernel in the truncated decode step")
    del eng

    t0 = time.time()
    plain, plain_handles = serve(model, params, prompts, None)
    t_plain = time.time() - t0
    same = [a.out_tokens == b.out_tokens
            for a, b in zip(handles, plain_handles)]
    log(f"[c] unshadowed run: {t_plain:.2f}s; tokens identical for "
        f"{sum(same)}/{len(same)} requests")
    require(all(same), "shadowed run served different tokens")
    del plain
    return {"shadowed_requests": len(shadowed), "decode_kernels": n_kernels}


def layer_period(cfg):
    """One layer period at full width: danube is uniform, so one layer."""
    return cfg.replace(n_layers=1)


def search_inputs(cfg):
    import jax
    import jax.numpy as jnp
    from repro.models import Model
    model = Model(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(SEED))
    toks = jax.random.randint(jax.random.PRNGKey(SEED + 1),
                              (SEARCH_BATCH, SEARCH_SEQ + 1), 0, cfg.vocab)
    batch = {"tokens": toks[:, :-1].astype(jnp.int32),
             "labels": toks[:, 1:].astype(jnp.int32)}
    return model, params, batch


def run_search(model, params, batch, mesh=None):
    from repro import search
    from repro.launch.compilation import count_compiles
    t0 = time.time()
    with count_compiles() as n:
        res = search.autosearch(model.loss, (params, batch),
                                search.loss_degradation, SEARCH_BUDGET,
                                threshold=5e-3, mesh=mesh)
    return res, n[0], time.time() - t0


def assignment_rows(res):
    return {p: (a.man_bits, a.excluded) for p, a in res.assignments.items()}


def phase_profile(cfg):
    """autosearch through the runtime table and memtrace, one layer."""
    import jax
    from repro.core import TruncationPolicy, memtrace
    from repro.core.interpreter import enumerate_sites, parameterized_callable

    model, params, batch = search_inputs(cfg)
    log(f"[d] {cfg.name} layer period: {cfg.n_layers} layer, "
        f"d_model={cfg.d_model}, batch {SEARCH_BATCH}x{SEARCH_SEQ}")

    # the runtime-table program autosearch dispatches must hold kernels
    closed, shape = jax.make_jaxpr(model.loss, return_shape=True)(
        params, batch)
    index = enumerate_sites(closed, TruncationPolicy.scoped("**", "e8m0"))
    _, run_batch = parameterized_callable(
        closed, jax.tree_util.tree_structure(shape), index)
    tables = np.stack([index.identity_table()] * 2)
    text = run_batch.lower(tables, jax.tree_util.tree_leaves(
        (params, batch))).as_text()
    n_kernels = text.count(KERNEL_MARKER)
    log(f"[d] runtime-table program: {len(index)} sites, "
        f"{n_kernels} {KERNEL_MARKER} ops")
    require(n_kernels > 0, "runtime-table program has no Pallas kernel")

    res, compiles, secs = run_search(model, params, batch)
    log(f"[d] autosearch: {secs:.2f}s, evals={res.evals_used} "
        f"dispatches={res.n_dispatches} compiles={compiles} "
        f"final_error={res.final_error:.3e} converged={res.converged}")
    for path, (m, excl) in assignment_rows(res).items():
        log(f"[d]   {path}: man_bits={m} excluded={excl}")
    require(res.evals_used > 0 and res.n_dispatches > 0, "search did no work")

    t0 = time.time()
    policy = TruncationPolicy.scoped("**/mlp", "e5m7")
    loss, report = memtrace(model.loss, policy, threshold=1e-3)(params, batch)
    loss = float(loss)
    log(f"[d] memtrace: {time.time() - t0:.2f}s, loss={loss:.4f}, "
        f"{len(report.locations)} locations")
    for loc, flags, err in report.top(3):
        log(f"[d]   {loc}: flags={flags} max_rel={err:.3e}")
    require(np.isfinite(loss), "memtrace loss is not finite")
    return {"evals": res.evals_used, "dispatches": res.n_dispatches,
            "compiles": compiles}


def phase_mesh_search(cfg, n_chips: int):
    """Candidate-parallel autosearch over a probe mesh vs one device."""
    from repro.launch.mesh import make_probe_mesh
    model, params, batch = search_inputs(cfg)
    mesh = make_probe_mesh(n_chips)
    single, c1, s1 = run_search(model, params, batch)
    sharded, c4, s4 = run_search(model, params, batch, mesh=mesh)
    log(f"[4] mesh=None: {s1:.2f}s evals={single.evals_used} "
        f"dispatches={single.n_dispatches} compiles={c1} "
        f"probes/dispatch/device={single.probes_per_dispatch_per_device}")
    log(f"[4] probe mesh {dict(mesh.shape)}: {s4:.2f}s "
        f"evals={sharded.evals_used} dispatches={sharded.n_dispatches} "
        f"compiles={c4} probes/dispatch/device="
        f"{sharded.probes_per_dispatch_per_device}")
    require(assignment_rows(single) == assignment_rows(sharded),
            "sharded search assigned differently")
    require(single.evals_used == sharded.evals_used,
            "sharded search used a different number of evals")
    return {"probes_per_dispatch_per_device":
            sharded.probes_per_dispatch_per_device}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    from repro.configs.base import get_config
    from repro.launch.compilation import use_compile_cache
    log(f"compile cache: {use_compile_cache()}")

    timings = {}

    def timed(name, fn, *a):
        t0 = time.time()
        out = fn(*a)
        timings[name] = round(time.time() - t0, 3)
        log(f"== phase {name}: {timings[name]}s")
        return out

    device = timed("device", phase_device, args.chips)
    cfg = get_config(ARCH, "full")
    if args.chips == 4:
        timed("mesh_search", phase_mesh_search, layer_period(cfg), 4)
    else:
        timed("kernels", phase_kernels)
        timed("serve", phase_serve, cfg)
        timed("profile", phase_profile, layer_period(cfg))
    log(f"phase seconds (wall, compile included): {json.dumps(timings)}")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
