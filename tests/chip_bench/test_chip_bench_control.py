"""The profiling cell's faults, planted in the program's loss, fail its
check; and the control, the reference in fp8 put in the program's place,
makes a run read ``correct: false`` through the harness's own comparison,
for each kind of cell, at a size a test run holds."""
import pytest

import repro.models.transformer as transformer

_loss = transformer.loss_fn
SEEDS = (2**31 + 1, 2**31 + 3, 3 * 10**9 + 7, 5, 4 * 10**9 + 1)


def loss_half_batch_left_out(params, batch, cfg):
    half = batch["tokens"].shape[0] // 2
    return _loss(params, {k: v[:half] for k, v in batch.items()}, cfg)


def loss_altered(params, batch, cfg):
    return _loss(params, batch, cfg) * 1.01


@pytest.mark.parametrize("fault,check", [
    (loss_half_batch_left_out, "report_elements_off"),
    (loss_altered, "loss_rel_gap")])
def test_profiling_fault_fails_the_check(small_cell, run_small, monkeypatch,
                                         fault, check):
    monkeypatch.setattr(transformer, "loss_fn", fault)
    res = run_small(small_cell("dscoder-memtrace"))
    assert res["correct"] is False
    assert res["checks"][check]["value"] > res["checks"][check]["limit"]


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("name,check", [
    ("danube-chat-plain", "max_logit_gap"),
    ("dscoder-memtrace", "loss_rel_gap")])
def test_control_reads_incorrect(small_cell, run_small, name, check, seed):
    res = run_small(small_cell(name), seed=seed, control=True)
    assert res["correct"] is False, res["checks"]
    assert res["checks"][check]["value"] > res["checks"][check]["limit"]
