"""Continuous-batching serving engine with sampled shadow profiling.

A compact production shape: fixed-size decode batch, slot-based request
table, per-slot position cursors in the cache (``cache["pos"]`` is (B,)),
so a new request prefills into any free slot *while other slots keep
decoding* — no all-slots-free barrier, no equal-prompt-length waves.
Quarantined slots are immediately reusable (admission zeroes exactly that
slot's cache lanes). Every tick is one call of a single jit'd decode step
whose signature never changes; :meth:`Engine.assert_zero_recompile` checks
the executable cache stays at one entry, the same discipline as the
guarded trainer.

Shadow profiling rides on top: a sampled fraction of requests decode
through the ``memtrace``-shadowed step against the deployed policy (see
:mod:`repro.serving.shadow`) — the served tokens stay bit-identical, the
paired lane feeds per-request and rolling RaptorReports, and drift against
the deployed artifact's accepted error budget pages a re-search hook.
"""
from __future__ import annotations

import dataclasses
import warnings
from collections import deque
from typing import Dict, Iterator, List, Optional

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.api import truncate
from repro.core.policy import resolve_policy
from repro.distributed.sharding import mesh_active
from repro.models import Model
from repro.serving.shadow import ShadowConfig, ShadowProfiler


@dataclasses.dataclass
class Request:
    """The handle :meth:`Engine.submit` returns; fields fill in as the
    request moves through the batch. ``report`` is the merged per-request
    RaptorReport when the request was shadow-sampled."""

    rid: int
    prompt: np.ndarray              # (S,) int32
    max_new_tokens: int = 32
    out_tokens: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    status: str = "ok"              # "ok" | "error_nonfinite"
    error: str = ""
    shadowed: bool = False
    report: Optional[object] = None  # merged RaptorReport (shadowed only)
    _fed: int = 0                    # prompt tokens already fed (prefill cursor)


class Engine:
    """``policy`` deploys the engine under a RAPTOR truncation policy —
    anything :func:`repro.core.policy.resolve_policy` accepts: a
    :class:`~repro.core.TruncationPolicy`, a flag string, a
    :class:`~repro.artifacts.PolicyArtifact`, or a registry ref like
    ``"bench_model@v3"``. The decode step is truncated once at
    construction; serving under an artifact is bit-identical to serving
    under its in-process policy because the artifact's JSON round trip is
    lossless.

    ``shadow`` (a :class:`~repro.serving.shadow.ShadowConfig`) enables
    sampled shadow profiling of live requests; the engine then exposes
    ``serving_report`` (rolling merged RaptorReport), ``drift_events``,
    and threads fired drift detections into ``self.artifact`` provenance.
    """

    def __init__(self, model: Model, params, batch_size: int = 8,
                 max_seq_len: int = 512, greedy: bool = True, policy=None,
                 shadow: Optional[ShadowConfig] = None, registry=None):
        self.model = model
        self.params = params
        self.B = batch_size
        self.S = max_seq_len
        self.greedy = greedy
        res = resolve_policy(policy, registry=registry)
        self.policy = res.policy
        self.artifact = res.artifact
        self.cache = model.init_cache(batch_size, max_seq_len)
        self.slots: List[Optional[Request]] = [None] * batch_size
        self.lengths = np.zeros(batch_size, np.int32)
        raw_step = model.decode_step
        step = raw_step
        if self.policy is not None:
            step = truncate(step, self.policy)
        # per-engine closures: jit caches key on the callable's identity, so
        # wrapping shared functions (the staticmethod reset, bound decode
        # methods) would alias executable caches across engines and break
        # the one-entry-per-engine assertion
        if mesh_active():
            self.cache = self._settle(step, params, self.cache)
        # the cache is donated to the decode step and to the slot reset, so
        # that each writes its rows in place instead of copying the cache
        self._decode = jax.jit(lambda p, c, t, _fn=step: _fn(p, c, t),
                               donate_argnums=(1,))
        self._reset = jax.jit(lambda c, s, _fn=self._slot_reset: _fn(c, s),
                              donate_argnums=(0,))
        self._shadow: Optional[ShadowProfiler] = None
        if shadow is not None:
            self._shadow = ShadowProfiler(raw_step, self.policy, shadow,
                                          artifact=self.artifact)
        self._queue: deque = deque()
        self._done: Dict[int, Request] = {}
        self._finished: deque = deque()
        self._next_rid = 0
        self._tick = 0
        # static counters of the dispatch span: the routed experts each MoE
        # layer holds here, and the bytes of an MLA latent cache
        cfg = model.cfg
        self._counters = {
            "experts_held": len(cfg.moe.held) if cfg.moe else 0,
            "latent_cache_bytes": sum(
                t.nbytes for t in jax.tree_util.tree_leaves(
                    {k: v for k, v in self.cache.items() if k != "pos"})
            ) if cfg.attn_type == "mla" else 0}

    def _settle(self, step, params, cache):
        """The cache in the layout the decode step settles on. Under a
        serving mesh the first decode re-shards the cache, so a cache whose
        layout changed between the first and second call would retrace
        every jit'd path: one warm-up decode through a throwaway jit, then
        the warmed cache zeroed in its own layout. Both take the cache
        donated, so set-up never holds a second cache."""
        warm = jax.jit(lambda p, c, t, _fn=step: _fn(p, c, t)[1],
                       donate_argnums=(1,))
        warmed = warm(params, cache, jnp.zeros((self.B,), jnp.int32))
        zero = jax.jit(lambda c: jax.tree_util.tree_map(jnp.zeros_like, c),
                       donate_argnums=(0,),
                       out_shardings=jax.tree_util.tree_map(
                           lambda t: t.sharding, warmed))
        return zero(warmed)

    # ---- request management ------------------------------------------------
    def submit(self, prompt=None, _legacy_prompt=None, *,
               max_new_tokens: int = 32, rid: Optional[int] = None
               ) -> Request:
        """Queue a request; returns its :class:`Request` handle. Request ids
        are auto-assigned; passing one explicitly (or the legacy positional
        ``submit(rid, prompt, ...)`` form) still works but is deprecated."""
        if _legacy_prompt is not None:
            # legacy positional form: submit(rid, prompt, max_new_tokens=...)
            warnings.warn(
                "Engine.submit(rid, prompt) is deprecated; call "
                "submit(prompt) and use the returned Request handle "
                "(explicit ids: submit(prompt, rid=...))",
                DeprecationWarning, stacklevel=2)
            rid, prompt = int(prompt), _legacy_prompt
        if rid is None:
            rid = self._next_rid
        prompt = np.asarray(prompt, np.int32)
        # validate HERE, not deep inside admission: a prompt that can never
        # fit the fixed cache must be rejected at the API boundary with a
        # clear error instead of silently running a slot cursor past
        # max_seq_len requests later.
        if prompt.ndim != 1 or prompt.size == 0:
            raise ValueError(
                f"request {rid}: prompt must be a non-empty 1-D token "
                f"array, got shape {prompt.shape}")
        if prompt.size > self.S - 1:
            raise ValueError(
                f"request {rid}: prompt of {prompt.size} tokens does not "
                f"fit max_seq_len={self.S} (at most {self.S - 1} prompt "
                "tokens leave room to decode at least one token)")
        if max_new_tokens < 1:
            raise ValueError(
                f"request {rid}: max_new_tokens must be >= 1, "
                f"got {max_new_tokens}")
        req = Request(rid, prompt, max_new_tokens)
        if self._shadow is not None:
            req.shadowed = self._shadow.sample()
        self._next_rid = max(self._next_rid, rid + 1)
        self._queue.append(req)
        return req

    @staticmethod
    def _slot_reset(cache, slot):
        """Zero exactly one batch lane of every cache leaf (jit'd once; the
        slot index is a traced scalar so admission never retraces). Stacked
        ``layers`` / encdec cross leaves carry batch at axis 1, everything
        else (pos, lead, global, recurrent states) at axis 0. The lane is
        zeroed, not only its cursor reset: a quarantined request can leave
        NaN there, which ``p @ v`` would carry even under a mask."""
        def zero_lane(axis):
            def fn(t):
                shape = t.shape[:axis] + (1,) + t.shape[axis + 1:]
                return jax.lax.dynamic_update_slice_in_dim(
                    t, jnp.zeros(shape, t.dtype), slot, axis)
            return fn
        out = {}
        for key, sub in cache.items():
            axis = 1 if key in ("layers", "cross_k", "cross_v") else 0
            out[key] = jax.tree_util.tree_map(zero_lane(axis), sub)
        return out

    def _admit(self) -> int:
        """Admit queued requests into free slots — continuously: any free
        (including just-quarantined) slot takes the next request while the
        other slots keep decoding. The slot's cache lanes are zeroed so the
        new request starts from a fresh cursor. Returns how many were
        admitted."""
        admitted = 0
        free = [s for s in range(self.B) if self.slots[s] is None]
        for s in free:
            if not self._queue:
                break
            req = self._queue.popleft()
            self.cache = self._reset(self.cache, jnp.int32(s))
            self.slots[s] = req
            self.lengths[s] = 0
            req._fed = 0
            admitted += 1
        return admitted

    def _finish(self, slot: int, req: Request):
        req.done = True
        self._done[req.rid] = req
        self._finished.append(req)
        self.slots[slot] = None
        self.lengths[slot] = 0

    # ---- decode loop -------------------------------------------------------
    def step(self) -> bool:
        """One tick: admit into free slots, then one token of work for every
        live slot — prompt tokens for slots still prefilling, the previous
        output token for decoding slots — through a single batched decode
        call. A slot emits its next output token on the tick that feeds its
        final prompt token (masked prefill and decode interleave freely).

        Each phase is a ``raptor/engine.*`` span in the profiler's trace
        (the README lists them), under one ``raptor/engine.step`` span per
        tick; ``raptor/engine.wait`` is where the host first blocks on the
        tick's device work."""
        with jax.profiler.StepTraceAnnotation("raptor/engine.step",
                                              step_num=self._tick):
            with jax.profiler.TraceAnnotation("raptor/engine.admit") as span:
                span.set_metadata(admitted=self._admit(),
                                  queued=len(self._queue))
            live = [s for s in range(self.B) if self.slots[s] is not None]
            if not live:
                return False
            with jax.profiler.TraceAnnotation("raptor/engine.feed"):
                tok = np.zeros((self.B,), np.int32)
                emitting = []
                for s in live:
                    req = self.slots[s]
                    if req._fed < len(req.prompt):
                        tok[s] = req.prompt[req._fed]
                        if req._fed == len(req.prompt) - 1:
                            emitting.append(s)
                    else:
                        tok[s] = req.out_tokens[-1]
                        emitting.append(s)
                tok = jnp.asarray(tok)

            shadow_live = [s for s in live if self.slots[s].shadowed]
            shadowed = self._shadow is not None and bool(shadow_live)
            report = None
            # in_place: the step took the cache donated and writes its new
            # rows in place; the decode and the shadowed step both do
            with jax.profiler.TraceAnnotation(
                    "raptor/engine.dispatch",
                    path="shadow" if shadowed else "decode", live=len(live),
                    emitting=len(emitting), in_place=1, **self._counters):
                if shadowed:
                    logits, self.cache, report = self._shadow.step(
                        self.params, self.cache, tok)
                else:
                    logits, self.cache = self._decode(self.params,
                                                      self.cache, tok)
                self.assert_zero_recompile()

            for s in live:
                req = self.slots[s]
                if req._fed < len(req.prompt):
                    req._fed += 1
                self.lengths[s] += 1

            # the tick's outputs go to the host in one round trip: the
            # logits, and on a shadowed tick the report, which observe merges
            # on the host. The copies are asked for before the host blocks,
            # so that the runtime starts them as soon as the step is done.
            outs = jax.tree_util.tree_leaves((logits, report))
            with jax.profiler.TraceAnnotation("raptor/engine.wait"):
                for x in outs:
                    x.copy_to_host_async()
                jax.block_until_ready(outs)
            with jax.profiler.TraceAnnotation(
                    "raptor/engine.fetch",
                    bytes=sum(x.nbytes for x in outs)):
                logits_np, report = jax.device_get((logits, report))
            if shadowed:
                with jax.profiler.TraceAnnotation("raptor/shadow.observe",
                                                  requests=len(shadow_live)):
                    self._shadow.observe(report,
                                         [self.slots[s] for s in shadow_live],
                                         self._tick)
                with jax.profiler.TraceAnnotation("raptor/shadow.check"):
                    event = self._shadow.check(self._tick)
                if event is not None and self.artifact is not None:
                    self.artifact = self._shadow.log.attach(self.artifact)

            with jax.profiler.TraceAnnotation("raptor/engine.sample"):
                nxt = np.argmax(logits_np, axis=-1)
                # quarantine non-finite decode: a slot whose logits went
                # NaN/Inf (numerically broken policy, corrupted params)
                # fails THAT request with a clear status and frees the slot
                # for the next admission — an argmax over NaN logits would
                # otherwise silently emit token 0 and poison the stream
                finite = np.isfinite(logits_np).all(axis=-1)
            with jax.profiler.TraceAnnotation("raptor/engine.emit") as span:
                emitted = finished = quarantined = 0
                for s in emitting:
                    req = self.slots[s]
                    if not finite[s]:
                        req.status = "error_nonfinite"
                        req.error = (f"non-finite logits while decoding "
                                     f"token {len(req.out_tokens) + 1} "
                                     f"(slot {s}); request quarantined")
                        self._finish(s, req)
                        quarantined += 1
                        continue
                    req.out_tokens.append(int(nxt[s]))
                    emitted += 1
                    if (len(req.out_tokens) >= req.max_new_tokens
                            or self.lengths[s] >= self.S - 1):
                        self._finish(s, req)
                        finished += 1
                span.set_metadata(emitted=emitted, finished=finished,
                                  quarantined=quarantined)
            self._tick += 1
            return True

    def run(self) -> Dict[int, Request]:
        while self._queue or any(s is not None for s in self.slots):
            self.step()
        return self._done

    def stream(self) -> Iterator[Request]:
        """Yield requests as they finish (completion order), instead of
        polling :meth:`run`'s dict."""
        while self._queue or any(s is not None for s in self.slots):
            self.step()
            while self._finished:
                yield self._finished.popleft()

    # ---- zero-recompile discipline ----------------------------------------
    def cache_sizes(self) -> Dict[str, Optional[int]]:
        """Executable-cache entry counts for every jit'd serving path
        (None before first use / where the runtime doesn't expose it)."""
        def size(fn):
            f = getattr(fn, "_cache_size", None)
            if f is None:
                return None
            n = int(f())
            return n if n else None
        out = {"decode": size(self._decode), "reset": size(self._reset)}
        if self._shadow is not None:
            n = self._shadow.cache_size()
            out["shadow"] = n if n else None
        return out

    def assert_zero_recompile(self):
        """The serving invariant: every jit'd path traced exactly once.
        Per-slot cursors keep the decode signature static across ragged
        admission, so any growth here is a bug (same check as the guarded
        trainer's)."""
        for name, n in self.cache_sizes().items():
            if n is not None and n > 1:
                raise AssertionError(
                    f"serving {name} step retraced: {n} executable cache "
                    "entries (expected 1) — the decode signature must not "
                    "depend on admission state")

    # ---- shadow-profiling surface ------------------------------------------
    @property
    def serving_report(self):
        """Rolling serving-side RaptorReport merged over every shadowed
        tick (None when shadow profiling is off / nothing sampled yet)."""
        return None if self._shadow is None else self._shadow.report

    @property
    def drift_events(self):
        return [] if self._shadow is None else list(self._shadow.events)

    @property
    def guardrail_log(self):
        return None if self._shadow is None else self._shadow.log
