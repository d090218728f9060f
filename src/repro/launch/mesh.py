"""Mesh construction from the devices present.

The production mesh puts model (tensor) parallelism inside a pod-slice row
of at most 16 chips and data parallelism across the rest: a 256-chip v5e
pod slice is (data=16, model=16) and one chip is (1, 1). Multi-pod adds a
leading ``pod`` axis of pure data parallelism across pods (slow DCI links
carry only the gradient all-reduce; params/optimizer are FSDP-sharded
within a pod): two pods are (pod=2, data=16, model=16).

FUNCTIONS, not module constants: importing this module must never touch
jax device state.
"""
from __future__ import annotations

from typing import Tuple

import jax
from jax.sharding import AxisType

POD_MODEL_PARALLEL = 16


def make_mesh(axis_shapes, axis_names, *, devices=None):
    """``jax.make_mesh`` with every axis ``Auto``: the repo places arrays
    through sharding rules and GSPMD, while ``jax.make_mesh`` defaults to
    explicit-sharding axes."""
    return jax.make_mesh(axis_shapes, axis_names,
                         axis_types=(AxisType.Auto,) * len(axis_names),
                         devices=devices)


def best_mesh_shape(n_devices: int, model_parallel: int) -> Tuple[int, int]:
    """Largest (data, model) grid for ``n_devices``: the model axis is the
    widest power-of-two fraction of ``model_parallel`` that divides it."""
    model = model_parallel
    while model > 1 and n_devices % model:
        model //= 2
    return n_devices // model, model


def make_production_mesh(*, multi_pod: bool = False,
                         n_devices: int | None = None):
    """(data, model) — or (pod, data, model) — mesh over the first
    ``n_devices`` devices present (default: all of them)."""
    devs = jax.devices()
    n = len(devs) if n_devices is None else n_devices
    if n > len(devs):
        raise ValueError(f"make_production_mesh: {n} devices requested, "
                         f"{len(devs)} visible")
    pods = 2 if multi_pod else 1
    if n % pods:
        raise ValueError(f"{n} devices do not split into {pods} pods")
    shape: Tuple[int, ...] = best_mesh_shape(n // pods, POD_MODEL_PARALLEL)
    axes: Tuple[str, ...] = ("data", "model")
    if multi_pod:
        shape, axes = (pods, *shape), ("pod", *axes)
    return make_mesh(shape, axes, devices=devs[:n])


def make_host_mesh(model_parallel: int = 1):
    """Small (data, model) mesh over whatever devices exist (tests / CPU
    runs)."""
    n = len(jax.devices())
    return make_mesh(best_mesh_shape(n, model_parallel), ("data", "model"))


def make_probe_mesh(n_devices: int | None = None, axis: str = "probe"):
    """1-D mesh for mesh-parallel profiling: the leading candidate axis of a
    (K, num_sites, 4) format-table batch is sharded over ``axis`` so a
    W-candidate ladder evaluates on W/ndev devices concurrently (see
    ``api.truncate_sweep(mesh=...)`` / ``search.autosearch(mesh=...)``).

    ``n_devices`` takes a prefix of ``jax.devices()`` (useful for measuring
    per-device-count throughput); default is every visible device. On CPU,
    emulate a multi-device host with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8``."""
    devs = jax.devices()
    if n_devices is not None:
        if n_devices > len(devs):
            raise ValueError(
                f"make_probe_mesh: {n_devices} devices requested, "
                f"{len(devs)} visible")
        devs = devs[:n_devices]
    return make_mesh((len(devs),), (axis,), devices=devs)


def make_profile_mesh(probe: int, data: int = 1, *,
                      axes=("probe", "data")):
    """2-D (probe, data) mesh: candidate-parallel x data-parallel profiling.
    ``probe * data`` must not exceed the visible device count."""
    n = probe * data
    devs = jax.devices()
    if n > len(devs):
        raise ValueError(f"make_profile_mesh: {n} devices requested, "
                         f"{len(devs)} visible")
    return make_mesh((probe, data), tuple(axes), devices=devs[:n])
