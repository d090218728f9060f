"""Production serving entrypoint: continuous batching with optional RAPTOR
truncation policy and sampled shadow profiling of live traffic.

    PYTHONPATH=src python -m repro.launch.serve --arch glm4-9b \
        [--policy "scope:**/mlp=fp16"] [--requests 8] [--new-tokens 16] \
        [--shadow-rate 0.0625] [--drift-margin 4.0] [--production]

``--production`` serves the full-size config on a mesh built from the
devices present (one v5e chip holds h2o-danube-1.8b whole); the default
``--smoke`` config is the reduced one.

Requests stream in with mixed prompt lengths and token budgets; the
engine admits each one into any free decode slot while the other slots
keep decoding (no aligned waves — see :mod:`repro.serving.engine`).

Policies deploy through :func:`repro.core.policy.resolve_policy`, the
single resolution path shared with ``launch.train`` and the guardrails:
an explicit ``--policy`` flag string, or — the profile→policy→deploy
handoff — a registry ref (``--policy-artifact bench_model@v3
[--registry artifacts]``) whose searched policy is applied to the decode
step, so the exact assignment a profiling run produced is what serves
traffic.

With ``--shadow-rate > 0`` a sampled fraction of requests decode through
the memtrace-shadowed step (served tokens stay bit-identical); the merged
serving-side RaptorReport is printed at drain, and drift past the
deployed artifact's accepted error budget pages a re-search suggestion
(top-blamed sites as an autosearch warm start) and is recorded in the
artifact's provenance.
"""
from __future__ import annotations

import argparse
import time

import numpy as np

import jax

from repro.artifacts import default_root
from repro.configs.base import get_config
from repro.core.policy import resolve_policy as _core_resolve_policy
from repro.distributed import sharding as shd
from repro.launch.compilation import use_compile_cache
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models import Model
from repro.models.common import ParamDef
from repro.serving import Engine, ShadowConfig


def resolve_policy(policy_flag, artifact_ref, registry_root=None):
    """Back-compat wrapper over :func:`repro.core.policy.resolve_policy`.
    Returns (policy, artifact_or_None) like the old serve-local helper."""
    try:
        res = _core_resolve_policy(policy_flag, artifact_ref,
                                   registry=registry_root)
    except ValueError as e:
        raise SystemExit(str(e))
    if res.ref is not None:
        print(f"loaded {res.artifact} from registry "
              f"{registry_root or default_root()!r}", flush=True)
    return res.policy, res.artifact


def _print_drift(event):
    """Re-search hook: surface the blame ranking as an autosearch warm
    start so the on-call can page a re-search with the live evidence."""
    print(f"DRIFT {event}", flush=True)
    warm = ",".join(loc for loc, _flags, _err in event.blame[:4])
    print(f"  re-search warm start: --warm-sites '{warm}'", flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--prompt-len", type=int, default=8,
                    help="mean prompt length (actual lengths are ragged)")
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--max-seq", type=int, default=64)
    ap.add_argument("--policy", default=None,
                    help='raw spec: "scope:**/mlp=fp16" or "32_to_5_14"')
    ap.add_argument("--policy-artifact", default=None,
                    help='registry ref: "name" (latest) or "name@v3"')
    ap.add_argument("--registry", default=None,
                    help=f"registry root (default $RAPTOR_REGISTRY or "
                         f"{default_root()!r})")
    ap.add_argument("--shadow-rate", type=float, default=0.0,
                    help="fraction of requests shadow-profiled (0 = off)")
    ap.add_argument("--shadow-threshold", type=float, default=1e-3,
                    help="memtrace flagging threshold for shadowed steps")
    ap.add_argument("--drift-margin", type=float, default=4.0,
                    help="page when peak shadow error exceeds margin x "
                         "the deployed artifact's accepted budget")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--production", dest="smoke", action="store_false")
    args = ap.parse_args()
    use_compile_cache()

    cfg = get_config(args.arch, "smoke" if args.smoke else "full")
    model = Model(cfg)
    # serving uses TP-only params (SERVE_PARAM_RULES)
    mesh = (make_host_mesh(model_parallel=2) if args.smoke
            else make_production_mesh())
    print(f"arch={cfg.name} layers={cfg.n_layers} d_model={cfg.d_model} "
          f"params={model.n_params() / 1e6:.1f}M mesh={dict(mesh.shape)} "
          f"devices={mesh.devices.flat[0].device_kind} x{mesh.size}",
          flush=True)
    with shd.use_mesh(mesh, param_rules=shd.SERVE_PARAM_RULES):
        defs = model.param_defs()
        sh = jax.tree_util.tree_map(
            lambda pd: shd.param_sharding(pd.shape, pd.axes, mesh),
            defs, is_leaf=lambda x: isinstance(x, ParamDef))
        params = jax.tree_util.tree_map(
            jax.device_put, model.init(jax.random.PRNGKey(0)), sh)

        policy, artifact = resolve_policy(args.policy, args.policy_artifact,
                                          args.registry)
        shadow = None
        if args.shadow_rate > 0 and policy is not None:
            shadow = ShadowConfig(rate=args.shadow_rate,
                                  threshold=args.shadow_threshold,
                                  drift_margin=args.drift_margin,
                                  on_drift=_print_drift)
        eng = Engine(model, params, batch_size=args.batch,
                     max_seq_len=args.max_seq,
                     policy=artifact if artifact is not None else policy,
                     shadow=shadow)
        rng = np.random.RandomState(0)
        for _ in range(args.requests):
            # ragged workload: prompts vary around --prompt-len so serving
            # exercises masked prefill into busy batches, not aligned waves
            plen = max(1, int(rng.randint(max(1, args.prompt_len // 2),
                                          args.prompt_len * 2)))
            eng.submit(rng.randint(1, cfg.vocab, plen),
                       max_new_tokens=args.new_tokens)
        t0 = time.time()
        done = eng.run()
        dt = time.time() - t0
        total = sum(len(r.out_tokens) for r in done.values())
        print(f"served {len(done)} requests, {total} tokens in {dt:.1f}s "
              f"({total / dt:.1f} tok/s on {mesh.size} devices)")
        for rid in sorted(done):
            req = done[rid]
            tag = " [shadowed]" if req.shadowed else ""
            tag += f" [{req.status}]" if req.status != "ok" else ""
            print(f"  req {rid}: {req.out_tokens}{tag}")
        if eng.serving_report is not None:
            top = eng.serving_report.top(3)
            print("shadow serving report (top sites):")
            for loc, flags, err in top:
                print(f"  {loc}: flags={flags} max_rel={err:.2e}")
        for ev in eng.drift_events:
            print(f"drift event recorded at tick {ev.tick} "
                  f"(peak {ev.peak:.2e} vs budget {ev.budget:.2e})")


if __name__ == "__main__":
    main()
