"""The comparison that decides ``correct`` fails when a serving cell's
timed path is broken underneath: each fault is planted in the program's
decode step or in the shadow report's merge, a small run is driven past
the look for a chip, and ``correct`` comes out false. No cell spans chips,
so there is no exchange to leave out."""
import jax.numpy as jnp
import pytest

import repro.models.transformer as transformer
from repro.serving import shadow as shadow_mod

CELLS = ["danube-chat-shadow", "danube-chat-plain"]
_decode = transformer.decode_step


def state_unchanged(params, cache, tokens, cfg, embeds=None):
    logits, _ = _decode(params, cache, tokens, cfg, embeds=embeds)
    return logits, cache


def half_batch_left_out(params, cache, tokens, cfg, embeds=None):
    logits, new = _decode(params, cache, tokens, cfg, embeds=embeds)
    half = logits.shape[0] // 2
    return jnp.concatenate([logits[:half], logits[:half]]), new


def token_altered(params, cache, tokens, cfg, embeds=None):
    logits, new = _decode(params, cache, tokens, cfg, embeds=embeds)
    top = jnp.argmax(logits, axis=-1)
    wrong = (top + 1) % logits.shape[-1]
    bump = jnp.max(logits, axis=-1) + 1.0
    return logits.at[jnp.arange(logits.shape[0]), wrong].set(bump), new


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("fault", [state_unchanged, half_batch_left_out,
                                   token_altered])
def test_fault_fails_the_check(small_cell, run_small, monkeypatch, name,
                               fault):
    monkeypatch.setattr(transformer, "decode_step", fault)
    res = run_small(small_cell(name))
    assert res["correct"] is False
    gap = res["checks"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]


def test_misreported_deviation_fails_the_report(small_cell, run_small,
                                                monkeypatch):
    observe = shadow_mod.ShadowProfiler.observe

    def out_of_bound(self, report, reqs, tick):
        report = type(report)(report.locations, report.flags,
                              report.max_rel + 3.0, report.op_counts)
        observe(self, report, reqs, tick)

    monkeypatch.setattr(shadow_mod.ShadowProfiler, "observe", out_of_bound)
    res = run_small(small_cell("danube-chat-shadow"))
    assert res["correct"] is False
    check = res["checks"]["report_max_rel"]
    assert check["value"] > check["limit"]


def test_dropped_shadow_tick_fails_the_report(small_cell, run_small,
                                              monkeypatch):
    seen = []
    observe = shadow_mod.ShadowProfiler.observe

    def drop_every_fifth(self, report, reqs, tick):
        seen.append(tick)
        if len(seen) % 5:
            observe(self, report, reqs, tick)

    monkeypatch.setattr(shadow_mod.ShadowProfiler, "observe",
                        drop_every_fifth)
    res = run_small(small_cell("danube-chat-shadow"))
    assert res["correct"] is False
    assert res["checks"]["report_elements_off"]["value"] > 0
