"""Serving cells of the MLA + MoE decoder (DeepSeek-V2-Lite as one
expert-parallel chip holds it): ``Engine`` under a policy, driven by
closed-loop clients.

The loop, the traffic and the window are those of ``serve_closed``; what
differs is the model. Its ``ArchConfig`` is built here from the
configuration file's published keys (latent attention with its YaRN RoPE,
the leading dense layers, routed experts of which this chip holds a share,
shared experts), its weights come from ``weights_mla_moe``, its operations
and bytes from ``flops_mla_moe`` and its reference from
``reference_mla_moe``. Keys the architecture does not publish (a sliding
window, a KV head count other than the attention heads') are not read.

Set-up ticks ``warm_ticks`` times before the window opens. A request of
this mix lives about 1,500 ticks (prompt tokens fed one a tick, then the
answer), and the run opens with every client's first request cut to a
share, so the first few hundred ticks are mostly prefill of short
contexts; the warm ticks carry the slots into the mix's steady state (by
the mix's lengths about 80% of slots emitting, live contexts near 700
positions), which the window then measures and logs.

``correct`` holds two comparisons with the reference, which holds the
same expert share. ``max_logit_gap``, as in ``serve_closed``: the widest
gap by which a served token's logit lies below the reference's best, over
a sample of finished requests (the longest among them). It reads which
token the program put first, so a fault that moves the logits less than
the gaps between them escapes it, as the held routed experts' part (8 of
64 experts, a few percent of the feed-forward) can at a small size.
``max_logit_error`` reads the logits themselves: after the window the
same engine ticks ``check_ticks`` more times with the decode step's
logits kept, and for a sample of the requests served then (the longest
among them) the logit the step gave each served token is held to the
reference's logit of that token. With ``control`` the fp8 reference
takes the program's place in both.

A traced run also saves the compiled decode step's HLO text, so that the
window's device ops are put down to the program's scopes (``scopes.py``),
and counts the weight and cache bytes that the MoE and MLA rooflines read.
"""
from __future__ import annotations

import gc
import shutil
import time
from typing import Dict

import numpy as np

from benchmarks.chip import (flops_mla_moe, harness, reference_mla_moe,
                             scopes, traffic, weights_mla_moe, xplane)
from benchmarks.chip.drivers.serve_closed import ClosedLoop, _sample
from benchmarks.chip.spec import Cell


def arch_config(cfg: dict):
    """The program's ``ArchConfig`` for an MLA + MoE configuration file."""
    from repro.configs.base import (ArchConfig, MLAConfig, MoEConfig,
                                    YarnScaling)
    rs = cfg.get("rope_scaling")
    yarn = None
    if rs:
        if rs.get("type") != "yarn":
            raise ValueError(f"no RoPE scaling {rs.get('type')!r}")
        yarn = YarnScaling(
            factor=float(rs["factor"]),
            original_max_position_embeddings=int(
                rs["original_max_position_embeddings"]),
            beta_fast=float(rs["beta_fast"]), beta_slow=float(rs["beta_slow"]),
            mscale=float(rs["mscale"]),
            mscale_all_dim=float(rs["mscale_all_dim"]))
    ep = cfg["expert_parallel"]
    held = tuple(int(e) for e in ep["held_experts"])
    if len(held) != cfg["n_routed_experts"]:
        raise ValueError("n_routed_experts is the count of held experts")
    heads = cfg["num_attention_heads"]
    return ArchConfig(
        name=cfg["name"], family="moe",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=heads, n_kv_heads=heads, head_dim=cfg["v_head_dim"],
        d_ff=cfg["moe_intermediate_size"], vocab=cfg["vocab_size"],
        attn_type="mla",
        mla=MLAConfig(q_lora=cfg["q_lora_rank"], kv_lora=cfg["kv_lora_rank"],
                      rope_head_dim=cfg["qk_rope_head_dim"],
                      nope_head_dim=cfg["qk_nope_head_dim"],
                      v_head_dim=cfg["v_head_dim"]),
        moe=MoEConfig(n_experts=int(ep["router_experts"]),
                      top_k=cfg["num_experts_per_tok"],
                      d_expert=cfg["moe_intermediate_size"],
                      n_shared=cfg["n_shared_experts"],
                      first_k_dense=cfg["first_k_dense_replace"],
                      d_ff_dense=cfg["intermediate_size"],
                      renormalize=bool(cfg["norm_topk_prob"]),
                      routed_scaling_factor=float(
                          cfg["routed_scaling_factor"]),
                      held_experts=held),
        rope_theta=float(cfg["rope_theta"]), rope_scaling=yarn,
        norm_eps=float(cfg["rms_norm_eps"]),
        tie_embeddings=bool(cfg.get("tie_word_embeddings", False)),
        dtype=cfg["torch_dtype"])


class LongGen(ClosedLoop):
    """``ClosedLoop`` with this model's operations, and the bytes the MLA
    roofline counts, summed over the traced ticks; over the window, the
    ticks and the live slots' context, which say how near the run is to
    its steady state."""

    def __init__(self, *args, **kw):
        super().__init__(*args, **kw)
        self.traced_ticks = 0
        self.mla_bytes = 0
        self.window_ticks = 0
        self.window_ctx = 0

    def tick(self) -> bool:
        eng = self.eng
        ctx = [int(eng.lengths[s]) + 1 for s, r in enumerate(eng.slots)
               if r is not None] + [1] * len(self.pending)
        flops0 = self.flops
        ok = super().tick()
        if ok and self.recording:
            self.window_ticks += 1
            self.window_ctx += sum(ctx)
        if ok and self.spans.tracing:
            self.flops = flops0 + sum(
                flops_mla_moe.decode_token_flops(self.cfg, c) for c in ctx)
            self.traced_ticks += 1
            self.mla_bytes += flops_mla_moe.mla_bytes(self.cfg, ctx)
        return ok


class ScopedWindow(harness.Window):
    """The traced window, reduced with each device op's scope."""

    def reduce(self, span_names=(), hlo_text=None):
        if self._dir is not None:
            self.trace = scopes.ScopedTrace(xplane.find_trace(self._dir),
                                            span_names, hlo_text=hlo_text)
            shutil.rmtree(self._dir, ignore_errors=True)
            self._dir = None
        return self.trace


class _Tap:
    """The engine's decode step, keeping the logits of its last call."""

    def __init__(self, fn):
        self.fn, self.logits = fn, None

    def __call__(self, *args):
        out = self.fn(*args)
        self.logits = out[0]
        return out

    def _cache_size(self):
        return self.fn._cache_size()


def _check_ticks(loop: LongGen, n: int) -> list:
    """``n`` more ticks of the same engine after the window, keeping the
    logit that the step gave each token it served: ``[(request, {index
    into its out tokens: logit})]``."""
    eng = loop.eng
    tap = _Tap(eng._decode)
    eng._decode = tap
    seen: Dict[int, tuple] = {}
    try:
        for _ in range(n):
            live = [(s, r) for s, r in enumerate(eng.slots) if r is not None]
            before = {r.rid: len(r.out_tokens) for _, r in live}
            if not loop.tick():
                break
            top = np.asarray(tap.logits, np.float32).max(axis=-1)
            for s, r in live:
                if len(r.out_tokens) > before[r.rid]:
                    seen.setdefault(r.rid, (r, {}))[1][
                        len(r.out_tokens) - 1] = float(top[s])
    finally:
        eng._decode = tap.fn
    return list(seen.values())


def _pick_checked(checked: list, n: int, seed: int) -> list:
    """The request with the longest context and the rest drawn from the
    seed, as ``(prompt, out tokens, {index: program's logit})``."""
    if not checked:
        return []
    longest = max(range(len(checked)), key=lambda i: len(
        checked[i][0].prompt) + len(checked[i][0].out_tokens))
    rest = [i for i in range(len(checked)) if i != longest]
    order = traffic.rng(seed).permutation(len(rest))
    pick = [longest] + [rest[i] for i in order[:max(0, n - 1)]]
    return [(np.asarray(checked[i][0].prompt),
             np.asarray(checked[i][0].out_tokens, np.int32), checked[i][1])
            for i in pick]


def _hlo_text(eng) -> str:
    """The compiled decode step's HLO text (from the compile cache)."""
    import jax.numpy as jnp
    tok = jnp.zeros((eng.B,), jnp.int32)
    return eng._decode.lower(eng.params, eng.cache, tok).compile().as_text()


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        t_start: float, control: bool = False) -> dict:
    import jax
    from repro.launch.compilation import count_compiles
    from repro.models import Model
    from repro.serving import Engine

    cfg, tr, sv = cell.config, cell.traffic, cell.config["serving"]
    spans = harness.Spans()
    model = Model(arch_config(cfg))
    params = weights_mla_moe.make(cfg, seed)
    jax.block_until_ready(params)
    harness.log(f"weights made: {time.time() - t_start:.3f} s since start, "
                f"device peak {harness.memory_peak_bytes()} B")
    eng = Engine(model, params, batch_size=sv["batch_slots"],
                 max_seq_len=sv["max_seq_len"], policy=sv["policy"])
    loop = LongGen(eng, traffic.closed_loop(tr, cfg["vocab_size"], seed),
                   cfg, spans, False)
    harness.log(f"engine built: {time.time() - t_start:.3f} s since start, "
                f"device peak {harness.memory_peak_bytes()} B")
    loop.open(tr["clients"])
    while loop.ticks < tr["warm_ticks"]:
        if not loop.tick():
            raise RuntimeError("set-up ran out of requests")
    setup_peak = harness.memory_peak_bytes()
    harness.log(f"set-up: {loop.ticks} warm ticks, executables "
                f"{eng.cache_sizes()}, device peak {setup_peak} B")

    window = ScopedWindow(spans, trace)
    trace_s = min(seconds, tr.get("trace_seconds", seconds))
    with harness.settled(), count_compiles() as compiles:
        t0 = time.perf_counter()
        setup_s = time.time() - t_start
        loop.recording = True
        window.start()
        while True:
            if not loop.tick():
                raise RuntimeError("the window ran out of requests")
            el = time.perf_counter() - t0
            if window.on and spans.tracing and el >= trace_s:
                window.stop()
            if el >= seconds:
                break
    window.stop()
    t1 = time.perf_counter()
    harness.log(f"window: {t1 - t0:.3f} s, {loop.ticks} ticks in all, "
                f"{loop.tokens} tokens, {len(loop.gaps)} gaps, "
                f"compiles in window {compiles[0]}")
    slot_ticks = max(1, loop.window_ticks * sv["batch_slots"])
    harness.log(f"window: {loop.window_ticks} ticks, emitting share "
                f"{loop.tokens / slot_ticks:.4f}, mean live context "
                f"{loop.window_ctx / slot_ticks:.1f} positions")
    mem_peak = harness.memory_peak_bytes()
    in_window = [r for r, t in loop.finished if t >= t0]
    e2e = {"decode_tok_s": loop.tokens / (t1 - t0),
           "itl_p95_ms": float(np.percentile(loop.gaps, 95)) * 1e3
           if loop.gaps else None,
           "setup_s": setup_s}
    # the step's HLO names each device op's scope; a CPU run has none
    hlo = (_hlo_text(eng) if trace and jax.devices()[0].platform == "tpu"
           else None)
    checked = _pick_checked(_check_ticks(loop, cell.limits["check_ticks"]),
                            cell.limits["sample_requests"], seed)

    sample = _sample([r for r, _ in loop.finished],
                     cell.limits["sample_requests"], seed, False)
    sample = [(np.asarray(r.prompt), np.asarray(r.out_tokens, np.int32))
              for r in sample]
    n = loop.traced_ticks
    ctx = {"spans": spans, "trace": None, "model_flops": loop.flops,
           "device_kind": jax.devices()[0].device_kind,
           "moe_bytes": n * flops_mla_moe.moe_weight_bytes(cfg),
           "mla_bytes": loop.mla_bytes}
    del eng, loop, params
    gc.collect()

    window.reduce(("engine_step",), hlo_text=hlo)
    if window.trace is not None:
        harness.log(f"scopes read from: {window.trace.source}")
    ctx["trace"] = window.trace
    ref_w = weights_mla_moe.make(cfg, seed)
    gaps = [float(np.max(reference_mla_moe.served_gaps(
        ref_w, cfg, sv["policy"], prompt, out, sv["max_seq_len"],
        control=control))) for prompt, out in sample]
    errs = [float(np.max(reference_mla_moe.served_logit_errors(
        ref_w, cfg, sv["policy"], prompt, out, logits, sv["max_seq_len"],
        control=control))) for prompt, out, logits in checked]
    served = sum(len(o) for _, o in sample)
    checks: Dict[str, dict] = {
        "max_logit_gap": {"value": max(gaps) if gaps else float("inf"),
                          "limit": cell.limits["max_logit_gap"]},
        "max_logit_error": {"value": max(errs) if errs else float("inf"),
                            "limit": cell.limits["max_logit_error"]}}
    harness.log(f"reference: {len(sample)} requests, {served} "
                f"{'control' if control else 'served'} tokens; "
                f"{len(checked)} requests, "
                f"{sum(len(c[2]) for c in checked)} logits of the check "
                f"ticks; memory peak {mem_peak} B, set-up's {setup_peak} B")
    return harness.result(cell, trace, e2e, ctx, checks,
                          attempted=len(in_window),
                          failed=sum(r.status != "ok" for r in in_window),
                          memory_peak_bytes=mem_peak)
