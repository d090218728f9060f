"""Shared set-up of the benchmark's own tests: the harness is imported as
``benchmarks.chip`` from the checkout's root, and its cells are rehearsed
on the CPU at a small size."""
import copy
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

CPU = {"platform": "cpu", "kind": "cpu", "count": 1}


def shrink(cell):
    """The cell at a size the CPU runs in seconds: danube's hidden size for
    both configurations, one layer, few heads, a small feed-forward and
    vocabulary, few slots and short traffic.

    A model this small reads other numbers than the full one, so the
    limits are this size's own, set from this size's readings (CPU, seeds
    as in ``test_chip_bench_control.py`` and seven more):
    ``max_logit_gap`` program 0 to 0.0129 (12 plain and 8 shadow seeds),
    fp8 control 0.232 to 0.704, limit 0.07; ``loss_rel_gap`` over all 8
    batches (one batch's fp8 shift is signed here, and can lie near 0)
    program 8.6e-5 to 2.58e-4, control 9.42e-4 to 2.20e-3 (12 seeds),
    limit 5e-4."""
    cell = copy.deepcopy(cell)
    cell.config.update(hidden_size=2560, num_hidden_layers=1,
                       num_attention_heads=4,
                       num_key_value_heads=1, intermediate_size=256,
                       vocab_size=512, sliding_window=16)
    if cell.traffic["kind"] == "memtrace":
        cell.traffic.update(batch=4, seq=256, batches=8, warm_steps=1,
                            trace_seconds=0.5, plain_steps=2, check_steps=8)
        cell.limits["loss_rel_gap"] = 5e-4
        return cell
    cell.limits["max_logit_gap"] = 0.07
    cell.config["serving"] = dict(cell.config["serving"], batch_slots=4,
                                  max_seq_len=64)
    # requests of a few ticks, so that some finish inside a short window
    # even on a loaded machine
    cell.traffic.update(
        clients=4, requests_per_client=100, warm_ticks=4, trace_seconds=0.5,
        prompt=dict(median=4, sigma=1.0, min=2, max=16),
        output=dict(median=5, sigma=0.7, min=2, max=16))
    if cell.traffic.get("shadow_rate"):
        cell.traffic["shadow_rate"] = 0.3
    return cell


@pytest.fixture
def small_cell():
    from benchmarks.chip import spec
    return lambda name: shrink(spec.load_cell(name))


@pytest.fixture
def run_small():
    """Drive one small run of a cell past the harness's look for a chip."""
    import time
    from benchmarks.chip.run import run_cell

    def run(cell, seed=2**31 + 11, seconds=2.0, trace=False, **kw):
        return run_cell(cell, seed, seconds, trace, time.time(), CPU, **kw)
    return run
