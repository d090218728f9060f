"""Serving cells: ``Engine`` under a policy, driven by closed-loop clients.

Set-up makes the weights from the seed, builds the engine (which compiles
its decode step), opens the clients' first requests and ticks until every
path the window uses has run: the decode step, the slot reset, and (with
shadow sampling) the shadowed step, which the engine compiles on its first
shadowed tick. The window then ticks for ``--seconds``. Each tick is one
``Engine.step``; a request that completes sends the next at once, so the
slots stay full.

``correct``: once the window has closed and the engine is freed, a sample
of the finished requests, drawn from the seed with the longest among them
(and a shadowed one, where there is one), goes through the reference over
its prompt and served tokens; the widest gap by which a served token's
logit lies below the reference's best is held to its limit. With shadow
sampling the report's element count is held to the reference's count of
what the policy rounds on the shadowed ticks, and its deviations to the
bound of 2 that the program's deviation measure states.

With ``control`` the fp8 reference takes the program's place in that
comparison: at each position of the same prompts and served tokens, the
token it puts first is held to the limit, and the run must read
``correct: false``.
"""
from __future__ import annotations

import gc
import time
from typing import Dict, List

import numpy as np

from benchmarks.chip import flops, harness, reference, traffic, weights
from benchmarks.chip.spec import Cell, arch_config


class ClosedLoop:
    """Clients on one engine: one request per slot, the next sent when one
    completes. Records, per tick, its host span, whether a shadowed request
    was live, the tokens emitted and the plain model's FLOPs."""

    def __init__(self, eng, requests, cfg: dict, spans: harness.Spans,
                 shadow_on: bool):
        self.eng, self.requests, self.cfg = eng, requests, cfg
        self.spans = spans
        self.shadow_on = shadow_on
        self.next = 0
        self.pending: List = []
        self.finished: List = []          # (request, finish time)
        self.last_emit: Dict[int, float] = {}
        self.ticks = 0
        self.shadow_ticks = 0
        self.recording = False
        self.tokens = 0
        self.gaps: List[float] = []
        self.flops = 0.0

    def send(self):
        if self.next >= len(self.requests):
            return
        prompt, budget = self.requests[self.next]
        self.next += 1
        self.pending.append(self.eng.submit(prompt, max_new_tokens=budget))

    def open(self, clients: int):
        for _ in range(clients):
            self.send()

    def tick(self) -> bool:
        """One engine tick; False once no request is left to serve."""
        eng = self.eng
        live = [r for r in eng.slots if r is not None] + self.pending
        if not live:
            return False
        shadowed = self.shadow_on and any(r.shadowed for r in live)
        ctx = [int(eng.lengths[s]) + 1 for s, r in enumerate(eng.slots)
               if r is not None] + [1] * len(self.pending)
        before = {r.rid: len(r.out_tokens) for r in live}
        names = ("engine_step.shadowed",) if shadowed else ()
        with self.spans.span("engine_step", *names):
            eng.step()
        now = time.perf_counter()
        self.pending = []
        self.ticks += 1
        self.shadow_ticks += shadowed
        if self.spans.tracing:
            self.flops += sum(flops.decode_token_flops(self.cfg, c)
                              for c in ctx)
        for r in live:
            if len(r.out_tokens) == before[r.rid]:
                continue
            if self.recording:
                self.tokens += 1
                if r.rid in self.last_emit:
                    self.gaps.append(now - self.last_emit[r.rid])
            self.last_emit[r.rid] = now
            if r.done:
                self.finished.append((r, now))
                self.send()
        return True


def _sample(finished, n: int, seed: int, want_shadowed: bool):
    """The longest finished request, a shadowed one where asked, and the
    rest drawn from the seed."""
    ok = [r for r in finished if r.status == "ok"]
    if not ok:
        return []
    longest = max(ok, key=lambda r: len(r.prompt) + len(r.out_tokens))
    pick = [longest]
    shadowed = [r for r in ok if r.shadowed and r is not longest]
    if want_shadowed and shadowed:
        pick.append(shadowed[0])
    rest = [r for r in ok if all(r is not p for p in pick)]
    order = traffic.rng(seed).permutation(len(rest))
    pick += [rest[i] for i in order[:max(0, n - len(pick))]]
    return pick


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        t_start: float, control: bool = False) -> dict:
    import jax
    from repro.launch.compilation import count_compiles
    from repro.models import Model
    from repro.serving import Engine, ShadowConfig

    cfg, tr, sv = cell.config, cell.traffic, cell.config["serving"]
    spans = harness.Spans()
    model = Model(arch_config(cfg))
    params = weights.make(cfg, seed)
    jax.block_until_ready(params)
    harness.log(f"weights made: {time.time() - t_start:.3f} s since start, "
                f"device peak {harness.memory_peak_bytes()} B")
    shadow = (ShadowConfig(rate=tr["shadow_rate"])
              if tr.get("shadow_rate") else None)
    eng = Engine(model, params, batch_size=sv["batch_slots"],
                 max_seq_len=sv["max_seq_len"], policy=sv["policy"],
                 shadow=shadow)
    loop = ClosedLoop(eng, traffic.closed_loop(tr, cfg["vocab_size"], seed),
                      cfg, spans, shadow is not None)
    harness.log(f"engine built: {time.time() - t_start:.3f} s since start, "
                f"device peak {harness.memory_peak_bytes()} B")
    loop.open(tr["clients"])
    # a shadowed tick compiles the shadow step, the next one the report's
    # merge: both belong to set-up
    while loop.ticks < tr["warm_ticks"] or (shadow and loop.shadow_ticks < 3):
        if not loop.tick():
            raise RuntimeError("set-up ran out of requests")
    harness.log(f"set-up: {loop.ticks} warm ticks, {loop.shadow_ticks} "
                f"shadowed, executables {eng.cache_sizes()}")

    window = harness.Window(spans, trace)
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
    mem_peak = harness.memory_peak_bytes()
    in_window = [r for r, t in loop.finished if t >= t0]
    e2e = {"decode_tok_s": loop.tokens / (t1 - t0),
           "itl_p95_ms": float(np.percentile(loop.gaps, 95)) * 1e3
           if loop.gaps else None,
           "setup_s": setup_s}

    checks: Dict[str, dict] = {}
    if shadow is not None:
        rep = eng.serving_report
        counted = int(np.asarray(rep.op_counts).sum()) if rep else 0
        want = (loop.shadow_ticks * sv["batch_slots"]
                * cfg["num_hidden_layers"]
                * reference.mlp_elements_per_token(cfg))
        worst = float(np.max(np.asarray(rep.max_rel))) if rep else 0.0
        harness.log(f"shadow report: {counted} elements over "
                    f"{loop.shadow_ticks} shadowed ticks; reference {want}")
        checks["report_elements_off"] = {"value": abs(counted - want),
                                         "limit": 0}
        checks["report_max_rel"] = {"value": worst,
                                    "limit": cell.limits["report_max_rel"]}

    sample = _sample([r for r, _ in loop.finished],
                     cell.limits["sample_requests"], seed, shadow is not None)
    sample = [(np.asarray(r.prompt), np.asarray(r.out_tokens, np.int32))
              for r in sample]
    ctx = {"spans": spans, "trace": None, "model_flops": loop.flops,
           "device_kind": jax.devices()[0].device_kind}
    del eng, loop, params
    gc.collect()

    window.reduce(("engine_step",))
    ctx["trace"] = window.trace
    ref_w = weights.make(cfg, seed)
    gaps = [float(np.max(reference.served_gaps(
        ref_w, cfg, sv["policy"], prompt, out, sv["max_seq_len"],
        control=control))) for prompt, out in sample]
    served = sum(len(o) for _, o in sample)
    checks["max_logit_gap"] = {"value": max(gaps) if gaps else float("inf"),
                               "limit": cell.limits["max_logit_gap"]}
    harness.log(f"reference: {len(sample)} requests, {served} "
                f"{'control' if control else 'served'} tokens")
    return harness.result(cell, trace, e2e, ctx, checks,
                          attempted=len(in_window),
                          failed=sum(r.status != "ok" for r in in_window),
                          memory_peak_bytes=mem_peak)
