"""Finds a cell's files by the names ``BENCHMARK.json`` gives them.

A configuration is ``configs/<config>.json`` (its ``file`` entry), a
traffic mix ``traffic/<traffic>.json``, a cell's correctness limits
``limits/<workload>.json``, a per-layer metric ``metrics/<name>.py`` and
the driver of a traffic mix's ``kind`` ``drivers/<kind>.py``. A later
cell or metric adds files; none of these is edited for it.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys
from typing import Callable, Dict, List

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: List[dict]
    per_layer: List[dict]


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: str = ROOT) -> Cell:
    bench = _load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    e2e = [m for m in bench["end_to_end"] if _reports(m, name)]
    moved = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if _reports(m, name) and m["moves"] in moved]
    return Cell(
        name=name, chips=int(w["chips"]),
        config=_load_json(os.path.join(root, conf["file"])),
        traffic=_load_json(os.path.join(HERE, "traffic",
                                        w["traffic"] + ".json")),
        limits=_load_json(os.path.join(HERE, "limits", name + ".json")),
        end_to_end=e2e, per_layer=layer)


def _module(folder: str, name: str):
    """``<folder>/<name>.py`` under the harness, loaded by its file name."""
    path = os.path.join(HERE, folder, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{folder}_{name}".replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(metric: str) -> Callable:
    """The ``read(ctx)`` function of ``metrics/<metric>.py``."""
    return _module("metrics", metric).read


def driver(kind: str) -> Callable:
    """The ``run(cell, seed, seconds, trace, t_start, control=False)``
    function of ``drivers/<kind>.py``, which drives a traffic mix of that
    ``kind`` through the program for one run."""
    return _module("drivers", kind).run


def arch_config(cfg: dict):
    """The program's ``ArchConfig`` for a configuration file (published
    key names, as in the model's ``config.json``)."""
    from repro.configs.base import ArchConfig
    heads = cfg["num_attention_heads"]
    return ArchConfig(
        name=cfg["name"], family="dense",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=heads, n_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg.get("head_dim") or cfg["hidden_size"] // heads,
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        sliding_window=cfg.get("sliding_window"),
        rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]),
        tie_embeddings=bool(cfg.get("tie_word_embeddings", False)),
        dtype=cfg["torch_dtype"])


def require_chips(n: int) -> Dict:
    """The device record, or exit non-zero: the benchmark measures a TPU
    and nothing else."""
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        sys.exit(f"no accelerator: {e}")
    if devs[0].platform != "tpu":
        sys.exit(f"no TPU: JAX found {devs[0].platform}")
    if len(devs) < n:
        sys.exit(f"{n} chips asked for, {len(devs)} present")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
