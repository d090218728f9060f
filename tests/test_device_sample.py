"""Each slot's greedy token is picked on the device, in the same call as the
decode step: the host is handed B token ids and B finite flags, never the
logits. Held here to a host ``np.argmax`` over the logits of the same call,
on a dense and an MLA + MoE model, plain, under a policy and shadowed;
with tied maxima (the first index wins, as ``np.argmax`` breaks ties) and a
NaN row (the request is quarantined as before). The last test holds the
benchmark driver's contract with the decode step: its first output is the
(B, V) logits, kept on the device, and it lowers with three arguments."""
import dataclasses
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, get_config
from repro.core import TruncationPolicy
from repro.models import Model
from repro.serving import Engine, ShadowConfig

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.chip import program_spans, xplane  # noqa: E402
from benchmarks.chip.drivers.serve_mla_moe import _Tap, _hlo_text  # noqa: E402

POLICY = TruncationPolicy.scoped("**/mlp", "e5m7")
ENGINES = {"plain": {},
           "policy": {"policy": POLICY},
           "shadow": {"policy": POLICY, "shadow": ShadowConfig(rate=1.0)}}


def _dense():
    return ArchConfig(name="pick", family="dense", n_layers=2, d_model=48,
                      n_heads=4, n_kv_heads=2, head_dim=12, d_ff=96,
                      vocab=64, dtype="float32", remat=False)


def _mla_moe():
    cfg = get_config("deepseek-v2-lite", "smoke")
    return cfg.replace(moe=dataclasses.replace(cfg.moe,
                                               held_experts=(0, 1, 2, 3)))


@pytest.fixture(scope="module", params=[_dense, _mla_moe],
                ids=["dense", "mla_moe"])
def served(request):
    cfg = request.param()
    model = Model(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(0))


class _Ticks:
    """Wraps a jitted step; each call keeps the slots' requests as the step
    saw them, how many tokens and what status each had, and the logits."""

    def __init__(self, eng, fn, logits_of, seen):
        self.eng, self.fn, self.logits_of, self.seen = eng, fn, logits_of, seen

    def __call__(self, *args):
        out = self.fn(*args)
        slots = list(self.eng.slots)
        self.seen.append((slots, [r and (len(r.out_tokens), r.status)
                                  for r in slots],
                          np.asarray(self.logits_of(out))))
        return out

    def _cache_size(self):
        return self.fn._cache_size()


def _serve(eng, work, seen):
    """Serve ``work`` tick by tick; after each tick hold every slot to the
    logits its step gave: an emitted token is the first index of the row's
    maximum over finite logits, a quarantine follows a non-finite row."""
    handles = [eng.submit(p, max_new_tokens=m) for p, m in work]
    while eng.step():
        slots, before, logits = seen[-1]
        for s, req in enumerate(slots):
            if req is None:
                continue
            n, status = before[s]
            row = logits[s]
            if len(req.out_tokens) > n:
                assert np.isfinite(row).all()
                assert req.out_tokens[-1] == int(np.argmax(row))
            elif req.status != status:
                assert req.status == "error_nonfinite"
                assert not np.isfinite(row).all()
    eng.assert_zero_recompile()
    return handles


def _workload(cfg, seed, lengths=(3, 7, 5, 9, 2), budgets=(4, 6, 3, 5, 8)):
    r = np.random.RandomState(seed)
    return [(r.randint(2, cfg.vocab, n).astype(np.int32), m)
            for n, m in zip(lengths, budgets)]


def _tied_and_poisoned(params, poison):
    """Every even token's head column copied to the next odd token, so that
    every row's maximum is tied; the embedding row of ``poison`` NaN."""
    head = params["lm_head"]
    head = head.at[:, 1::2].set(head[:, 0::2])
    embed = params["embed"].at[poison].set(jnp.nan)
    return {**params, "lm_head": head, "embed": embed}


@pytest.mark.parametrize("engine", sorted(ENGINES))
def test_served_token_is_the_host_argmax(served, engine):
    cfg, model, params = served
    eng = Engine(model, params, batch_size=3, max_seq_len=32,
                 **ENGINES[engine])
    seen = []
    eng._decode = _Ticks(eng, eng._decode, lambda o: o[0], seen)
    if eng._shadow is not None:
        eng._shadow._step = _Ticks(eng, eng._shadow._step,
                                   lambda o: o[0][0], seen)

    handles = _serve(eng, _workload(cfg, seed=1), seen)
    assert all(h.status == "ok" and len(h.out_tokens) == m
               for h, (_, m) in zip(handles, _workload(cfg, seed=1)))
    if engine == "shadow":
        assert eng._shadow.shadow_ticks == len(seen)

    # the same engine, with every row's maximum tied and one request's
    # prompt holding a token whose embedding is NaN
    poison = 1
    eng.params = _tied_and_poisoned(params, poison)
    work = _workload(cfg, seed=2)
    work[2] = (np.concatenate([work[2][0], [poison]]).astype(np.int32),
               work[2][1])
    ticks = len(seen)
    handles = _serve(eng, work, seen)
    for _, _, logits in seen[ticks:]:
        rows = logits[np.isfinite(logits).all(-1)]
        top = np.argmax(rows, -1)
        assert (top % 2 == 0).all()
        assert (rows[np.arange(len(rows)), top]
                == rows[np.arange(len(rows)), top + 1]).all()
    bad = handles[2]
    assert bad.status == "error_nonfinite" and bad.out_tokens == []
    assert bad.error.startswith("non-finite logits while decoding token 1")
    for h, (_, m) in zip(handles, work):
        if h is not bad:
            assert h.status == "ok" and len(h.out_tokens) == m
            assert all(t % 2 == 0 for t in h.out_tokens)


def test_decode_step_keeps_the_logits_for_the_driver(tmp_path):
    """What ``drivers/serve_mla_moe.py`` reads of the engine: the decode
    step's first output is the (B, V) float logits, whose row maximum is the
    logit of the token served; the step lowers with three arguments; and a
    plain tick's ``raptor/engine.fetch`` moves B token ids and B flags."""
    cfg = _mla_moe()
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    B = 4
    eng = Engine(model, params, batch_size=B, max_seq_len=32, policy=POLICY)
    tap = _Tap(eng._decode)
    eng._decode = tap
    handles = [eng.submit(p, max_new_tokens=m)
               for p, m in _workload(cfg, seed=3, lengths=(2, 4, 3, 5))]
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(xplane.WINDOW_SPAN):
        served = 0
        while eng.step():
            logits = tap.logits
            assert logits.shape == (B, cfg.vocab)
            assert logits.dtype == jnp.float32
            top = np.asarray(logits).max(-1)
            for s, r in enumerate(eng.slots):
                if r is not None and r.out_tokens:
                    assert np.asarray(logits)[s, r.out_tokens[-1]] == top[s]
                    served += 1
    jax.profiler.stop_trace()
    assert served and all(h.status == "ok" for h in handles)
    eng._decode = tap.fn
    assert "HloModule" in _hlo_text(eng)
    eng.assert_zero_recompile()

    tr = program_spans.ProgramTrace(xplane.find_trace(str(tmp_path)))
    fetched = [c[3]["bytes"] for _, children in tr.trees()
               for c in children if c[2] == "raptor/engine.fetch"]
    assert fetched and set(fetched) == {B * 5}
