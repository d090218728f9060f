"""Production training entrypoint.

On a real fleet each host runs:
    python -m repro.launch.train --arch glm4-9b --steps 100000 --production \
        --ckpt gs://bucket/run1 [--coordinator host:port --num-hosts N]
``--production`` trains the full-size config on a mesh built from the
devices present (a pod slice, or one chip); the default ``--smoke`` runs
the identical code path with a reduced config on the host mesh.

Covers the large-scale-runnability contract end-to-end: distributed init,
production mesh, FSDP×TP param placement, deterministic host-sharded data,
grad accumulation, checkpoint/restart supervision with straggler
monitoring, and an optional RAPTOR truncation policy as a first-class
config.
"""
from __future__ import annotations

import argparse
import os
import time

import jax
import jax.numpy as jnp

from repro.artifacts import ArtifactRef, Registry, default_root
from repro.guardrails import (
    EscalationLadder, FaultPlan, FaultSpec, GuardrailLog,
    NumericalFaultError, StepMonitor,
)
from repro.checkpoint.checkpointer import Checkpointer
from repro.configs.base import get_config
from repro.core import TruncationPolicy
# parse_policy/resolve_policy live in repro.core.policy (one grammar for
# every entrypoint); parse_policy re-exported for backward compatibility
from repro.core.policy import parse_policy, resolve_policy  # noqa: F401
from repro.data.pipeline import DataConfig, Pipeline, Prefetcher
from repro.distributed import sharding as shd
from repro.distributed.fault_tolerance import (
    StragglerMonitor, SupervisorConfig, run_supervised,
)
from repro.launch.compilation import use_compile_cache
from repro.launch.mesh import make_production_mesh, make_host_mesh
from repro.models import Model
from repro.models.common import ParamDef
from repro.optim.adamw import AdamWConfig, warmup_cosine
from repro.train.trainer import (
    TrainConfig, make_hotswap_train_step, make_train_step, init_opt_state,
)


def _parse_fault(spec: str) -> FaultSpec:
    """``--inject-fault SITE:STEP[:KIND]`` (KIND: overflow | bitflip)."""
    parts = spec.split(":")
    if len(parts) not in (2, 3):
        raise SystemExit(
            f"bad --inject-fault {spec!r}; want SITE:STEP[:KIND]")
    kind = parts[2] if len(parts) == 3 else "overflow"
    return FaultSpec(site=int(parts[0]), step=int(parts[1]), kind=kind)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=1000)
    ap.add_argument("--seq", type=int, default=None)
    ap.add_argument("--global-batch", type=int, default=None)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt", default="/tmp/repro_ckpt")
    ap.add_argument("--save-every", type=int, default=100)
    ap.add_argument("--policy", default=None,
                    help='RAPTOR spec: "32_to_5_14" or "scope:**/mlp=e5m7"')
    ap.add_argument("--policy-artifact", default=None,
                    help='registry ref ("name" or "name@v3"): train under '
                         "the artifact's searched policy via runtime format "
                         "tables (hot-swappable, zero recompile)")
    ap.add_argument("--swap-artifact", action="append", default=[],
                    metavar="STEP:REF",
                    help="hot-swap to registry artifact REF at STEP "
                         "(repeatable; requires --policy-artifact)")
    ap.add_argument("--guardrails", action="store_true",
                    help="runtime numerical guardrails: per-step divergence "
                         "monitor + precision-escalation ladder + "
                         "checkpoint rollback (requires --policy-artifact)")
    ap.add_argument("--inject-fault", action="append", default=[],
                    metavar="SITE:STEP[:KIND]",
                    help="chaos demo: corrupt table row SITE at STEP "
                         "(KIND: overflow | bitflip; repeatable; requires "
                         "--guardrails)")
    ap.add_argument("--registry", default=None,
                    help=f"artifact registry root (default $RAPTOR_REGISTRY "
                         f"or {default_root()!r})")
    ap.add_argument("--smoke", action="store_true", default=True,
                    help="reduced config on the host mesh (CPU container)")
    ap.add_argument("--production", dest="smoke", action="store_false")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--coordinator", default=None)
    ap.add_argument("--num-hosts", type=int, default=1)
    ap.add_argument("--host-id", type=int, default=0)
    args = ap.parse_args()
    use_compile_cache()

    if args.coordinator:
        jax.distributed.initialize(args.coordinator, args.num_hosts,
                                   args.host_id)

    variant = "smoke" if args.smoke else "full"
    cfg = get_config(args.arch, variant)
    model = Model(cfg)
    mesh = (make_host_mesh(model_parallel=2) if args.smoke
            else make_production_mesh(multi_pod=args.multi_pod))
    seq = args.seq or (128 if args.smoke else 4096)
    gbatch = args.global_batch or (8 if args.smoke else 256)
    print(f"arch={cfg.name} params={model.n_params()/1e6:.1f}M mesh={dict(mesh.shape)} "
          f"devices={mesh.devices.flat[0].device_kind} x{mesh.size} "
          f"seq={seq} batch={gbatch}", flush=True)

    # ---- precision-policy resolution --------------------------------------
    # --policy bakes a flag policy into the trace; --policy-artifact loads a
    # registry artifact and routes through runtime format tables instead, so
    # --swap-artifact can deploy a different artifact mid-run with zero
    # recompiles (the table is a step argument, not trace state). Both flags
    # funnel through the shared repro.core.policy.resolve_policy grammar.
    if args.swap_artifact and not args.policy_artifact:
        raise SystemExit("--swap-artifact requires --policy-artifact "
                         "(the runtime-table training path)")
    if args.guardrails and not args.policy_artifact:
        raise SystemExit("--guardrails requires --policy-artifact (the "
                         "escalation ladder rewrites the runtime table)")
    if args.inject_fault and not args.guardrails:
        raise SystemExit("--inject-fault requires --guardrails")
    registry = Registry(args.registry) if args.policy_artifact else None
    try:
        res = resolve_policy(args.policy, args.policy_artifact,
                             registry=registry)
    except ValueError as e:
        raise SystemExit(str(e))
    artifact, artifact_ref = res.artifact, res.ref
    swap_schedule = {}
    if artifact_ref is not None:
        print(f"policy artifact: {artifact_ref.ref} "
              f"(digest {artifact_ref.digest[:12]})", flush=True)
        for spec in args.swap_artifact:
            at, _, ref = spec.partition(":")
            swap_schedule[int(at)] = registry.load_ref(ref)

    tc = TrainConfig(
        optimizer=AdamWConfig(lr=args.lr),
        grad_accum=1 if args.smoke else cfg.grad_accum,
        # artifact policies deploy via runtime tables below, not the trace
        policy=res.policy if artifact is None else None,
        lr_schedule=lambda s: warmup_cosine(
            s, peak_lr=args.lr, warmup=min(2000, args.steps // 10 + 1),
            total=args.steps))
    data = Pipeline(DataConfig(
        seq_len=seq, global_batch=gbatch, vocab=cfg.vocab,
        d_model=cfg.d_model,
        input_mode=("encdec" if cfg.family == "encdec" else cfg.input_mode),
        mrope=cfg.rope_type == "mrope"))
    ck = Checkpointer(args.ckpt, keep_k=3)

    with shd.use_mesh(mesh):
        defs = model.param_defs()
        sh = jax.tree_util.tree_map(
            lambda pd: shd.param_sharding(pd.shape, pd.axes, mesh),
            defs, is_leaf=lambda x: isinstance(x, ParamDef))
        params = jax.tree_util.tree_map(
            jax.device_put, model.init(jax.random.PRNGKey(0)), sh)
        opt = init_opt_state(model, params, tc)
        state = {"params": params, "opt": opt}
        pf = Prefetcher(data)
        peeked = []   # first prefetched batch, reused as the trace example

        if artifact is not None:
            peeked.append({k: jnp.asarray(v) for k, v in pf.next().items()})
            # sites = the union of every artifact this run may deploy, so a
            # swap is always a subset of the enumerated table rows
            site_rules = tuple(artifact.policy.rules) + tuple(
                r for art, _ in swap_schedule.values()
                for r in art.policy.rules)
            hot_step, sites = make_hotswap_train_step(
                model, tc, TruncationPolicy(rules=site_rules),
                state["params"], peeked[0])
            step_fn = jax.jit(hot_step)
            active = {"ref": artifact_ref,
                      "table": sites.table_for(artifact.policy)}
        else:
            step_fn = jax.jit(make_train_step(model, tc))
            sites = active = None

        # ---- runtime numerical guardrails ---------------------------------
        # Monitor every step's loss/finiteness; on alarm, escalate blamed
        # sites in the live table (zero recompiles) and roll back through the
        # existing run_supervised machinery (NumericalFaultError is a
        # RuntimeError, the supervisor's default retry class).
        guard = None
        if args.guardrails:
            glog = GuardrailLog()
            guard = {
                "monitor": StepMonitor(),
                "ladder": EscalationLadder(active["table"], site_index=sites,
                                           log=glog),
                "plan": FaultPlan([_parse_fault(s)
                                   for s in args.inject_fault]),
                "log": glog,
                "escalated": None,
            }

        def restore_fn() -> int:
            latest = ck.latest_step()
            if guard is not None:
                guard["monitor"].reset()
            if latest is None:
                return 0
            (state["params"], state["opt"]), manifest = ck.restore(
                (state["params"], state["opt"]))
            data.load_state_dict(manifest["extra"]["data"])
            rec = manifest.get("policy_artifact")
            if rec and active is not None:
                # resume under the exact policy the checkpoint trained on:
                # reload by recorded name and verify the content digest
                art = registry.load(f"{rec['name']}@v{rec['version']}")
                if art.digest != rec["digest"]:
                    raise RuntimeError(
                        f"registry artifact {rec['name']}@v{rec['version']} "
                        f"digest {art.digest[:12]} != checkpoint-recorded "
                        f"{rec['digest'][:12]}; refusing to resume under a "
                        "different policy than the one trained on")
                active["ref"] = ArtifactRef.from_json(rec)
                active["table"] = sites.table_for(art.policy)
                print(f"[supervisor] resumed policy {active['ref'].ref}",
                      flush=True)
            if guard is not None and guard["escalated"] is not None:
                # the ladder's widened rows survive the rollback — resuming
                # under the pre-escalation table would just diverge again
                active["table"] = guard["escalated"]
            print(f"[supervisor] restored step {latest}", flush=True)
            return latest

        def save_fn(step: int):
            ck.save(step, (state["params"], state["opt"]),
                    extra={"data": data.state_dict()},
                    policy_artifact=active["ref"] if active else None)

        t0 = time.time()

        def step_fn_supervised(step: int):
            if active is not None and step in swap_schedule:
                art, ref = swap_schedule[step]
                active["ref"] = ref
                active["table"] = sites.table_for(art.policy)
                print(f"[policy] step {step}: hot-swapped to {ref.ref} "
                      "(runtime table, zero recompile)", flush=True)
            if guard is not None:
                table, fired = guard["plan"].apply(active["table"], step)
                for f in fired:
                    guard["log"].record(
                        step, "fault_injected", site=f.site, fault=f.kind,
                        row=[int(x) for x in table[f.site]])
                    print(f"[guardrail] step {step}: injected {f.kind} "
                          f"fault at site {f.site}", flush=True)
                active["table"] = table
            batch = (peeked.pop() if peeked
                     else {k: jnp.asarray(v) for k, v in pf.next().items()})
            extra = (active["table"],) if active is not None else ()
            state["params"], state["opt"], m = step_fn(
                state["params"], state["opt"], batch, jnp.int32(step), *extra)
            loss = float(m["loss"])
            if guard is not None:
                v = guard["monitor"].update(
                    step, loss, nonfinite=bool(m.get("nonfinite", False)))
                if v.alarm:
                    print(f"[guardrail] step {step}: ALARM — {v.reason}",
                          flush=True)
                    table, rollback = guard["ladder"].escalate(
                        active["table"], step, v)
                    active["table"] = guard["escalated"] = table
                    if rollback:
                        guard["log"].record(step, "rollback", reason=v.reason)
                        raise NumericalFaultError(v.reason)
            if step % 10 == 0:
                print(f"step {step:6d} loss {loss:.4f} "
                      f"gnorm {float(m['grad_norm']):.3f} "
                      f"({(time.time()-t0):.0f}s)", flush=True)
            return loss

        try:
            final, restarts, straggles = run_supervised(
                step_fn_supervised, save_fn, restore_fn, args.steps,
                SupervisorConfig(save_every=args.save_every),
                monitor=StragglerMonitor())
            ck.wait()
            print(f"done: step={final} restarts={restarts} "
                  f"straggles={straggles}", flush=True)
            if guard is not None:
                glog = guard["log"]
                log_path = os.path.join(args.ckpt, "guardrail_log.json")
                glog.save(log_path)
                print(glog.summary(), flush=True)
                print(f"[guardrail] log saved to {log_path}", flush=True)
                if artifact is not None and len(glog):
                    # the audited artifact: the deployed policy plus what the
                    # controller did while it ran
                    audited = glog.attach(artifact)
                    art_path = os.path.join(args.ckpt,
                                            "guardrail_artifact.json")
                    with open(art_path, "w") as f:
                        f.write(audited.dumps() + "\n")
                    print(f"[guardrail] audited artifact saved to "
                          f"{art_path}", flush=True)
        finally:
            pf.close()


if __name__ == "__main__":
    main()
