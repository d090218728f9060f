"""Rehearsal of ``dsv2lite-longgen`` at one dense and two MoE layers: the
served path through ``Engine`` reads ``correct: true``; the fp8 control in
the program's place, and faults planted in the latent cache's write, the
shared experts and the held routed experts, each read ``correct: false``
through the harness's own comparison.

Leaving the held routed experts out, or running them under the wrong
expert ids, moves the logits too little to change which token comes first
at most positions: over 8 seeds ``max_logit_gap`` read 0 to 0.061 for
those faults here, under the limit on most seeds. The comparison that
catches them is ``max_logit_error``, the program's logits of the tokens it
served held to the reference's.

The widths are this size's own (hidden 256, 4 heads, latent 64, rope 16,
expert width 64, vocabulary 512; the router still scores 64 experts and 8
are held), so the limits are this size's own, set from its readings on the
CPU over the seeds below and three more: ``max_logit_gap`` program 0 to
0.0017, fp8 control 0.051 to 0.183, limit 0.02; ``max_logit_error``
program 0.0028 to 0.0048, the two routed-expert faults 0.0170 to 0.0656,
fp8 control 0.096 to 0.141, limit 0.01."""
import copy

import jax.numpy as jnp
import pytest

import repro.models.attention as attention
import repro.models.moe as moe

SEEDS = (2**31 + 11, 2**31 + 3, 3 * 10**9 + 7, 5, 4 * 10**9 + 1)


@pytest.fixture
def cell():
    from benchmarks.chip import spec
    cell = copy.deepcopy(spec.load_cell("dsv2lite-longgen"))
    cell.config.update(
        num_hidden_layers=3, hidden_size=256, num_attention_heads=4,
        kv_lora_rank=64, qk_rope_head_dim=16, qk_nope_head_dim=32,
        v_head_dim=32, intermediate_size=256, moe_intermediate_size=64,
        vocab_size=512)
    cell.config["serving"] = dict(cell.config["serving"], batch_slots=4,
                                  max_seq_len=64)
    cell.traffic.update(
        clients=4, requests_per_client=100, warm_ticks=4, trace_seconds=0.5,
        prompt=dict(median=4, sigma=1.0, min=2, max=16),
        output=dict(median=5, sigma=0.7, min=2, max=16))
    cell.limits["max_logit_gap"] = 0.02
    cell.limits["max_logit_error"] = 0.01
    return cell


def _incorrect(res):
    return res["correct"] is False and any(
        c["value"] > c["limit"] for c in res["checks"].values())


def test_served_path_reads_correct(cell, run_small):
    res = run_small(cell, trace=True)
    assert res["correct"] is True, res["checks"]
    assert set(res["checks"]) == {"max_logit_gap", "max_logit_error"}
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"tick_ms.serve"}


@pytest.mark.parametrize("seed", SEEDS)
def test_control_reads_incorrect(cell, run_small, seed):
    assert _incorrect(run_small(cell, seed=seed, control=True))


def latent_never_written(cache, rows, pos):
    return cache


def shared_experts_left_out(x, wi, wo):
    return jnp.zeros_like(x)


def rows_to_the_wrong_slot(cache, rows, pos):
    # each slot's latent rows written into the next slot's lane
    rows = {n: jnp.roll(r, 1, axis=-2) for n, r in rows.items()}
    return _write(cache, rows, pos)


def held_experts_left_out(p, x, ids, gates, held):
    return jnp.zeros(x.shape, jnp.float32)


def wrong_held_ids(p, x, ids, gates, held):
    # the held weights taken for the experts one id above their own
    return _held(p, x, ids, gates, tuple(e + 1 for e in held))


_write = attention.mla_write
_held = moe._held_dense


@pytest.mark.parametrize("module,name,fault", [
    (attention, "mla_write", latent_never_written),
    (moe, "_ffn", shared_experts_left_out),
    (attention, "mla_write", rows_to_the_wrong_slot),
    (moe, "_held_dense", held_experts_left_out),
    (moe, "_held_dense", wrong_held_ids)])
def test_fault_fails_the_check(cell, run_small, monkeypatch, module, name,
                               fault):
    monkeypatch.setattr(module, name, fault)
    assert _incorrect(run_small(cell))


# --------------------------------------------------------------------------
# operations, bytes and scopes kept with the benchmark
# --------------------------------------------------------------------------

def test_operations_and_bytes_by_hand():
    from benchmarks.chip import flops_mla_moe, spec
    cfg = spec.load_cell("dsv2lite-longgen").config
    d, h, e, f, v = 2048, 16, 64, 1408, 102400
    attn = (d * h * 192            # q, no LoRA
            + d * 576              # latent and rope key
            + 512 * h * 256        # latent up to each head's key and value
            + h * 128 * d)         # o
    assert attn == flops_mla_moe.attention_params(cfg) == 13_762_560
    expert = 3 * d * f
    moe = 2 * d * e + 2 * expert * 6 * 8 / 64 + 2 * expert * 2
    want = 27 * 2 * attn + 2 * 3 * d * 10944 + 26 * moe + 2 * d * v
    assert flops_mla_moe.matmul_flops_per_token(cfg) == want
    # expanded attention: scores over 192, weighted sum over 128, 16 heads
    assert flops_mla_moe.attention_flops(cfg, 100) == 27 * 2 * 16 * 320 * 100
    # a tick reads the routers, 8 held and 2 shared experts of 26 layers
    assert flops_mla_moe.moe_weight_bytes(cfg) == \
        26 * (d * e + 10 * expert) * 2 == 4_505_206_784
    # and each layer's attention, and the latent rows of the live slots
    row = 576 * 2
    assert flops_mla_moe.mla_bytes(cfg, [1, 10]) == \
        27 * ((attn + 2 * d + 512) * 2 + 11 * row)


def _scoped(hlo_text):
    from benchmarks.chip import scopes
    from conftest import ROOT
    import os
    path = os.path.join(ROOT, "benchmarks", "chip", "testdata",
                        "serve_spans.xplane.pb.gz")
    return scopes.ScopedTrace(path, ("engine_step",), hlo_text=hlo_text)


def test_scopes_from_the_steps_hlo_text():
    """Device ops of a v5e trace (danube's, the only one kept) put down to
    scopes through instruction names and result types in an HLO text."""
    from benchmarks.chip import scopes, xplane
    bare = _scoped(None)
    events = [(s, e, n) for s, e, n in bare.ops[0]
              if xplane.op_key(n) == "quantize_em"]
    names = sorted({n for _, _, n in events})[:3]
    hlo = "\n".join(
        n.split(" custom-call(")[0] + " custom-call(%x), metadata={op_name="
        f'"jit(f)/while/body/layer/moe/experts/mlp/mul" stack_frame_id=1}}'
        for n in names)
    tr = _scoped(hlo)
    assert tr.source == "hlo" and bare.source is None
    want = sum(e - s for s, e, n in events if n in names) * 1e-9
    assert tr.scope_time_s("moe") == pytest.approx(want)
    assert tr.scope_time_s("moe/experts") == pytest.approx(want)
    assert tr.scope_time_s("mla") == 0.0
    assert bare.scope_time_s("moe") is None
    ctx = {"trace": tr, "device_kind": "TPU v5 lite"}
    assert scopes.roofline(ctx, "moe", 819e9 * want / 2) == \
        pytest.approx(50.0)
    assert scopes.roofline(ctx, "mla", 1e9) is None
    assert scopes.roofline(dict(ctx, trace=bare), "moe", 1e9) is None
    assert [n for n, _ in tr.breakdown()["device_scopes"]][0] == "other"
    assert not scopes.in_scope("a/moe_x/b", "moe")


def test_scope_metrics_silent_on_other_traces():
    """The roofline readers read nothing where the trace has no scopes."""
    from benchmarks.chip import spec, xplane
    from conftest import ROOT
    import os
    path = os.path.join(ROOT, "benchmarks", "chip", "testdata",
                        "serve_spans.xplane.pb.gz")
    ctx = {"trace": xplane.Trace(path, ("engine_step",)),
           "device_kind": "TPU v5 lite", "moe_bytes": 1e9, "mla_bytes": 1e9}
    for name in ("moe_roofline.dsv2lite", "mla_roofline.dsv2lite"):
        assert spec.reader(name)(ctx) is None
