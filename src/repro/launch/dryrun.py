"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Proves the distribution config is coherent without hardware: for each cell
we build abstract inputs (ShapeDtypeStruct, no allocation), lower the
train/prefill/serve step under the production mesh, compile it, and record
``memory_analysis`` (fits-per-device proof), ``cost_analysis`` (FLOPs/bytes
for the roofline) and the collective-op byte census parsed from the
optimized HLO.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch glm4-9b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod-only]
Artifacts land in experiments/dryrun/<arch>__<shape>__<mesh>.json.
"""
import argparse
import os
import json
import re
import time
import traceback
from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.configs.base import (
    ArchConfig, InputShape, SHAPES, ARCH_IDS, get_config, cells,
)
from repro.core import counters
from repro.search.scopes import discover_scopes
from repro.distributed import sharding as shd
from repro.launch.mesh import make_production_mesh
from repro.launch import specs as sp
from repro.models import Model
from repro.train.trainer import TrainConfig, make_train_step
from repro.optim.adamw import AdamWConfig

_POD_CHIPS = 256  # one v5e pod slice

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")

# m/v dtype per arch (memory fit for the 236B single-pod case)
_STATE_DTYPE = {"deepseek-v2-236b": "bfloat16"}

_COLLECTIVE_RE = re.compile(
    r"(\w[\w\.\-]*) = (\([^)]*\)|\S+) (all-reduce|all-gather|reduce-scatter|"
    r"all-to-all|collective-permute)(-start)?\(")
_SHAPE_RE = re.compile(r"(f64|f32|bf16|f16|f8e4m3fn|f8e5m2|s32|u32|s8|u8|pred|"
                       r"s64|u64|s16|u16)\[([0-9,]*)\]")

_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4,
          "s64": 8, "u64": 8, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
          "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}


_COMP_RE = re.compile(r"^(?:ENTRY )?%?([\w\.\-]+)[^\n{]*\{", re.M)
_WHILE_RE = re.compile(
    r"while\(.*?\), condition=%?([\w\.\-]+), body=%?([\w\.\-]+)")
_TRIP_RE = re.compile(r"constant\((\d+)\)")


def _split_computations(hlo_text: str) -> Dict[str, str]:
    comps: Dict[str, str] = {}
    pos = 0
    for m in _COMP_RE.finditer(hlo_text):
        start = m.end()
        depth = 1
        i = start
        while depth and i < len(hlo_text):
            c = hlo_text[i]
            if c == "{":
                depth += 1
            elif c == "}":
                depth -= 1
            i += 1
        comps[m.group(1)] = hlo_text[start:i]
    return comps


def _direct_bytes(body: str) -> Dict[str, float]:
    by_kind: Dict[str, float] = {}
    for m in _COLLECTIVE_RE.finditer(body):
        shape_str, kind = m.group(2), m.group(3)
        nbytes = 0
        for t, dims in _SHAPE_RE.findall(shape_str):
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            nbytes += n * _BYTES.get(t, 4)
        by_kind[kind] = by_kind.get(kind, 0) + nbytes
    return by_kind


def collective_census(hlo_text: str) -> Dict[str, Any]:
    """Sum result-shape bytes of every collective in the optimized HLO,
    expanding while-loop bodies by their trip counts (scan collectives
    execute `length` times; a static census would undercount a scanned layer
    stack by ~n_layers x)."""
    comps = _split_computations(hlo_text)
    entry = max(comps, key=lambda k: ("ENTRY %" + k in hlo_text
                                      or "ENTRY " + k in hlo_text,
                                      len(comps[k])))

    def trip_count(cond_name: str) -> int:
        body = comps.get(cond_name, "")
        consts = [int(x) for x in _TRIP_RE.findall(body)]
        return max(consts) if consts else 1

    def expand(name: str, seen) -> Dict[str, float]:
        if name in seen or name not in comps:
            return {}
        seen = seen | {name}
        total = dict(_direct_bytes(comps[name]))
        for m in _WHILE_RE.finditer(comps[name]):
            cond, body = m.group(1), m.group(2)
            trips = trip_count(cond)
            inner = expand(body, seen)
            for k, v in inner.items():
                total[k] = total.get(k, 0) + trips * v
        return total

    by_kind = expand(entry, frozenset())
    count = {}
    for m in _COLLECTIVE_RE.finditer(hlo_text):
        count[m.group(3)] = count.get(m.group(3), 0) + 1
    return {"bytes_by_kind": by_kind, "count_by_kind": count,
            "total_bytes": sum(by_kind.values())}


def _trainable_step(model: Model, cfg: ArchConfig):
    tc = TrainConfig(
        optimizer=AdamWConfig(
            state_dtype=_STATE_DTYPE.get(cfg.name, "float32")),
        grad_accum=cfg.grad_accum)
    return make_train_step(model, tc), tc


def _serve_rules(model: Model):
    """TP-only param sharding for serving when bf16 weights fit one
    model-parallel shard group (<=12 GB/dev leaves room for the cache);
    otherwise keep FSDP (deepseek-v2-236b)."""
    bytes_per_dev = model.n_params() * 2 / 16
    if bytes_per_dev <= 12e9:
        return shd.SERVE_PARAM_RULES
    return None


def lower_cell(arch_id: str, shape: InputShape, multi_pod: bool):
    cfg = get_config(arch_id)
    model = Model(cfg)
    mesh = make_production_mesh(
        multi_pod=multi_pod, n_devices=_POD_CHIPS * (2 if multi_pod else 1))
    param_rules = (None if shape.kind == "train" else _serve_rules(model))
    with shd.use_mesh(mesh, param_rules=param_rules):
        if shape.kind == "train":
            step_fn, tc = _trainable_step(model, cfg)
            params = sp.params_specs(model, mesh)
            opt = sp.opt_state_specs(
                model, mesh, _STATE_DTYPE.get(cfg.name, "float32"))
            batch = sp.input_specs(cfg, shape, mesh)
            step = jax.ShapeDtypeStruct((), jnp.int32)
            args = (params, opt, batch, step)
            lowered = jax.jit(step_fn).lower(*args)
            fn = step_fn
        elif shape.kind == "prefill":
            params = sp.params_specs(model, mesh)
            batch = sp.input_specs(cfg, shape, mesh, with_labels=False)
            args = (params, batch)
            lowered = jax.jit(model.prefill).lower(*args)
            fn = model.prefill
        else:  # decode
            params = sp.params_specs(model, mesh)
            cache = sp.cache_specs(model, shape, mesh)
            toks, emb = sp.decode_token_specs(cfg, shape, mesh)
            if emb is not None:
                fn = lambda p, c, t, e: model.decode_step(p, c, t, embeds=e)
                args = (params, cache, toks, emb)
            else:
                fn = model.decode_step
                args = (params, cache, toks)
            lowered = jax.jit(fn).lower(*args)
    return lowered, mesh, model, fn, args


def jaxpr_counts(fn, args):
    """Global FLOP/byte totals with scan trip counts folded in (XLA's
    cost_analysis counts while-loop bodies once). Returns
    (flops, bytes_unfused, bytes_fused, scope_census): the census is the
    precision-search work-list for this cell — the ``named_scope`` frontier
    ``repro.search.autosearch`` would assign formats to, with FLOP shares."""
    closed = jax.make_jaxpr(fn)(*args)
    rep = counters.count_jaxpr(closed.jaxpr, policy=None)
    rep_f = counters.count_jaxpr(closed.jaxpr, policy=None, fused=True)
    census = [
        {"scope": s.path, "flops": s.flops, "n_eqns": s.n_eqns,
         "fraction": round(s.fraction, 4)}
        for s in discover_scopes(closed, min_fraction=0.02, max_scopes=16)]
    return (rep.total_flops, sum(rep.bytes_by_fmt.values()),
            sum(rep_f.bytes_by_fmt.values()), census)


def model_flops(model: Model, shape: InputShape) -> float:
    """Paper-style MODEL_FLOPS: 6·N_active·D for training, 2·N_active·D for
    a prefill forward, 2·N_active·B per decoded token."""
    n = model.n_active_params()
    if shape.kind == "train":
        return 6.0 * n * shape.global_batch * shape.seq_len
    if shape.kind == "prefill":
        return 2.0 * n * shape.global_batch * shape.seq_len
    return 2.0 * n * shape.global_batch


def run_cell(arch_id: str, shape_name: str, multi_pod: bool,
             out_dir: str = OUT_DIR) -> Dict[str, Any]:
    shape = SHAPES[shape_name]
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    tag = f"{arch_id}__{shape_name}__{mesh_name}"
    rec: Dict[str, Any] = {"arch": arch_id, "shape": shape_name,
                           "mesh": mesh_name, "ok": False}
    t0 = time.time()
    try:
        lowered, mesh, model, fn, args = lower_cell(arch_id, shape, multi_pod)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        jflops, jbytes, jbytes_fused, scope_census = jaxpr_counts(fn, args)

        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):  # pre-0.5 jax: list of one dict
            cost = cost[0] if cost else {}
        census = collective_census(compiled.as_text())
        rec.update(
            ok=True,
            lower_s=round(t_lower, 2), compile_s=round(t_compile, 2),
            n_devices=mesh.size,
            n_params=model.n_params(),
            n_active_params=model.n_active_params(),
            jaxpr_flops=jflops,
            jaxpr_bytes=jbytes,
            jaxpr_bytes_fused=jbytes_fused,
            precision_search_scopes=scope_census,
            model_flops=model_flops(model, shape),
            memory={
                "argument_bytes": mem.argument_size_in_bytes,
                "output_bytes": mem.output_size_in_bytes,
                "temp_bytes": mem.temp_size_in_bytes,
                "alias_bytes": mem.alias_size_in_bytes,
                "code_bytes": mem.generated_code_size_in_bytes,
            },
            flops=cost.get("flops", 0.0),
            bytes_accessed=cost.get("bytes accessed", 0.0),
            collectives=census,
        )
    except Exception as e:  # noqa: BLE001 — a failed cell is a bug; record it
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    rec["total_s"] = round(time.time() - t0, 2)
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"{tag}.json"), "w") as f:
        json.dump(rec, f, indent=1)
    status = "OK " if rec["ok"] else "FAIL"
    print(f"[{status}] {tag}  ({rec['total_s']}s)"
          + ("" if rec["ok"] else f"  {rec['error']}"), flush=True)
    return rec


def main():
    # fake CPU devices for the pod meshes: set before JAX first looks for
    # devices, and appended so any flags the user set survive
    os.environ["XLA_FLAGS"] = " ".join(filter(None, (
        os.environ.get("XLA_FLAGS"),
        f"--xla_force_host_platform_device_count={2 * _POD_CHIPS}")))
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--single-pod-only", action="store_true")
    ap.add_argument("--multi-pod-only", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=OUT_DIR)
    args = ap.parse_args()

    jobs = []
    archs = [args.arch] if args.arch else list(ARCH_IDS)
    for arch in archs:
        for shape, runnable in cells(arch):
            if args.shape and shape.name != args.shape:
                continue
            if not runnable:
                print(f"[SKIP] {arch}__{shape.name} — full-attention arch, "
                      "long-context cell skipped", flush=True)
                continue
            meshes = []
            if not args.multi_pod_only:
                meshes.append(False)
            if not args.single_pod_only:
                meshes.append(True)
            if args.multi_pod:
                meshes = [True]
            for mp in meshes:
                jobs.append((arch, shape.name, mp))

    results = [run_cell(a, s, m, args.out) for a, s, m in jobs]
    ok = sum(r["ok"] for r in results)
    print(f"\n{ok}/{len(results)} cells compiled", flush=True)
    if ok < len(results):
        raise SystemExit(1)


if __name__ == "__main__":
    main()
