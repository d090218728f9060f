"""Profiling cells: ``memtrace`` of the model's loss, step after step.

Set-up makes the weights and ``batches`` packed batches on the device from
the seed, wraps ``model.loss`` in ``memtrace`` under the traffic's policy
once, and runs ``warm_steps`` steps, the first of which compiles it. The
window then steps through the batches in turn. Steps are sent ahead of the
one waited for, ``ahead_seconds`` of them at the last warm step's time, so
that the device keeps working while the host stands still; each step's
loss and report reach the host that many steps after it was sent. Once
``--seconds`` have passed (and at least ``check_steps`` steps were sent),
nothing more is sent, every step sent is read on the host, and only then
is the clock read: ``profile_step_ms`` is all of that time over all of
those steps. The traced run sends ``trace_seconds`` of steps under the
profiler and reads them all before the trace ends, then times the plain
``jax.jit(model.loss)`` the same way on the same batches, for the
profiling overhead.

``correct``: once the window has closed and the program's state is freed,
the reference computes the loss of the policy's definition (float32,
results of the MLP's operations rounded to the policy's grid) on a sample
of the window's steps drawn from the seed, and each step's truncated-lane
loss is held to it. Each sampled step's report is held to the reference's
count of what the policy rounds, and its deviations to the bound of 2 that
the program's deviation measure states.

With ``control`` the fp8 reference's loss of each sampled batch takes the
place of the program's, and the run must read ``correct: false``.
"""
from __future__ import annotations

import collections
import gc
import math
import time
from typing import Dict, List, Optional

import numpy as np

from benchmarks.chip import flops, harness, reference, traffic, weights
from benchmarks.chip.spec import Cell, arch_config


class Ahead:
    """Calls sent ahead of the one waited for: ``send`` queues a call's
    outputs and, while more than ``ahead`` are outstanding, reads the
    oldest on the host. ``done`` holds what was read, in order."""

    def __init__(self, ahead: int, spans: harness.Spans, name: str):
        import jax
        self.ahead, self.spans, self.name = ahead, spans, name
        self._get = jax.device_get
        self.pending: collections.deque = collections.deque()
        self.done: List = []
        self._marks: List[float] = []

    def send(self, outputs) -> None:
        if not self._marks:
            self._marks.append(time.perf_counter())
        self.pending.append(outputs)
        while len(self.pending) > self.ahead:
            self._read()

    def drain(self) -> None:
        while self.pending:
            self._read()

    def _read(self) -> None:
        with self.spans.span(self.name):
            self.done.append(self._get(self.pending.popleft()))
        self._marks.append(time.perf_counter())

    def step_ms(self) -> Optional[float]:
        """Median time between two reads (the first from the first send):
        with the queue full, the device's time per call, which a host
        stall moves in one interval only."""
        gaps = np.diff(self._marks)
        return float(np.median(gaps)) * 1e3 if len(gaps) else None


def make_batches(cfg: dict, tr: dict, seed: int):
    """``batches`` of ``batch`` sequences of ``seq`` tokens, made on the
    device from the seed: (tokens, labels) shifted by one."""
    import jax
    import jax.numpy as jnp
    n, b, s = tr["batches"], tr["batch"], tr["seq"]

    @jax.jit
    def build(key):
        toks = jax.random.randint(key, (n, b, s + 1), 0, cfg["vocab_size"],
                                  jnp.int32)
        return toks[..., :-1], toks[..., 1:]

    key = jax.random.fold_in(weights.key_from_seed(seed), 1)
    toks, labels = build(key)
    return [{"tokens": toks[i], "labels": labels[i]} for i in range(n)]


def reference_loss(w, cfg: dict, policy: str, batch: dict,
                   lowp: bool = False) -> float:
    """Mean token cross-entropy of the reference over one batch, a
    sequence at a time so that it fits beside nothing else."""
    toks = np.asarray(batch["tokens"])
    labels = np.asarray(batch["labels"])
    items = reference.cfg_items(cfg)
    total = sum(float(reference.nll_sum(w, toks[i], labels[i], items, policy,
                                        lowp)) for i in range(len(toks)))
    return total / toks.size


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        t_start: float, control: bool = False) -> dict:
    import jax
    from repro.core import memtrace
    from repro.core.policy import parse_policy
    from repro.launch.compilation import count_compiles
    from repro.models import Model

    cfg, tr = cell.config, cell.traffic
    spans = harness.Spans()
    model = Model(arch_config(cfg))
    params = weights.make(cfg, seed)
    batches = make_batches(cfg, tr, seed)
    jax.block_until_ready((params, batches))
    harness.log(f"weights and batches made: {time.time() - t_start:.3f} s "
                f"since start, device peak {harness.memory_peak_bytes()} B")
    step = memtrace(model.loss, parse_policy(tr["policy"]),
                    threshold=tr["threshold"])

    def send(i: int):
        loss, report = step(params, batches[i % len(batches)])
        return loss, report.op_counts, report.max_rel

    for i in range(tr["warm_steps"]):
        w0 = time.perf_counter()
        jax.device_get(send(i))
        warm_s = time.perf_counter() - w0
    ahead = max(2, math.ceil(tr["ahead_seconds"] / warm_s))
    harness.log(f"set-up: {tr['warm_steps']} warm steps, the last "
                f"{warm_s * 1e3:.3f} ms; {step.cache_size()} memtrace "
                f"executable; {ahead} steps sent ahead")

    window = harness.Window(spans, trace)
    traced_steps = math.ceil(tr.get("trace_seconds", seconds) / warm_s)
    queue = Ahead(ahead, spans, "memtrace_read")
    sent = 0
    with harness.settled(), count_compiles() as compiles:
        t0 = time.perf_counter()
        setup_s = time.time() - t_start
        window.start()
        while True:
            with spans.span("memtrace_send"):
                out = send(sent)
            sent += 1
            queue.send(out)
            over = time.perf_counter() - t0 >= seconds and \
                sent >= tr["check_steps"]
            if spans.tracing and (sent >= traced_steps or over):
                queue.drain()
                traced_steps, memtrace_ms = sent, queue.step_ms()
                window.stop()
            if over:
                break
        queue.drain()
        t1 = time.perf_counter()
    window.stop()
    results = queue.done
    n = len(results)
    harness.log(f"window: {t1 - t0:.3f} s, {n} steps, compiles in window "
                f"{compiles[0]}")
    mem_peak = harness.memory_peak_bytes()
    e2e = {"profile_step_ms": (t1 - t0) / n * 1e3, "setup_s": setup_s}
    ctx = {"spans": spans, "trace": None,
           "device_kind": jax.devices()[0].device_kind}
    if trace:
        ctx["model_flops"] = traced_steps * flops.sequence_flops(
            cfg, tr["batch"], tr["seq"])
        plain = jax.jit(model.loss)
        jax.block_until_ready(plain(params, batches[0]))
        timed = Ahead(ahead, spans, "plain_read")
        for i in range(tr["plain_steps"]):
            timed.send(plain(params, batches[i % len(batches)]))
        timed.drain()
        ctx["memtrace_overhead_x"] = memtrace_ms / timed.step_ms()

    # the sample: the window's first step and others drawn from the seed
    picks = sorted({0, *traffic.rng(seed).choice(
        n, size=min(n, tr["check_steps"]) - 1, replace=False).tolist()})
    sampled = [(i % len(batches), results[i]) for i in picks]
    del step, params
    gc.collect()
    window.reduce(("memtrace_send", "memtrace_read"))
    ctx["trace"] = window.trace

    ref_w = weights.make(cfg, seed)
    want = tr["batch"] * tr["seq"] * cfg["num_hidden_layers"] * \
        reference.mlp_elements_per_token(cfg)
    refs: Dict[int, float] = {}
    lows: Dict[int, float] = {}
    gap, off, worst = 0.0, 0, 0.0
    for b, (loss, counts, max_rel) in sampled:
        if b not in refs:
            refs[b] = reference_loss(ref_w, cfg, tr["policy"], batches[b])
            if control:
                lows[b] = reference_loss(ref_w, cfg, tr["policy"],
                                         batches[b], lowp=True)
        if control:
            loss = lows[b]         # the control in the program's place
        gap = max(gap, abs(float(loss) - refs[b]) / abs(refs[b]))
        off = max(off, abs(int(np.sum(counts)) - want))
        worst = max(worst, float(np.max(max_rel)))
    harness.log(f"reference: {len(sampled)} steps over {len(refs)} batches")
    lim = cell.limits
    checks = {"loss_rel_gap": {"value": gap, "limit": lim["loss_rel_gap"]},
              "report_elements_off": {"value": off, "limit": 0},
              "report_max_rel": {"value": worst,
                                 "limit": lim["report_max_rel"]}}
    failed = sum(not np.isfinite(loss) for loss, _, _ in results)
    return harness.result(cell, trace, e2e, ctx, checks, attempted=n,
                          failed=failed, memory_peak_bytes=mem_peak)
