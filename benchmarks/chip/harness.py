"""What every driver shares: the compile cache, host spans, the traced
window, the device's memory peak, and the result line."""
from __future__ import annotations

import contextlib
import shutil
import statistics
import sys
import tempfile
import time
from typing import Dict, List, Optional

from benchmarks.chip import spec, xplane


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def use_compile_cache() -> str:
    """The program's own cache choice (``$JAX_COMPILATION_CACHE_DIR``, else
    ``<checkout>/.jax_cache``), with every executable kept, so that only a
    checkout's first run of a cell compiles."""
    import jax
    from repro.launch.compilation import use_compile_cache as program_cache
    path = program_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


class Spans:
    """Host-clock spans of the harness's calls into the program, and a
    ``TraceAnnotation`` of each while the profiler runs."""

    def __init__(self):
        self.durations: Dict[str, List[float]] = {}
        self.tracing = False

    @contextlib.contextmanager
    def span(self, name: str, *also: str):
        import jax
        ann = (jax.profiler.TraceAnnotation(name) if self.tracing
               else contextlib.nullcontext())
        t0 = time.perf_counter()
        with ann:
            yield
        dt = time.perf_counter() - t0
        if self.tracing:
            for n in (name,) + also:
                self.durations.setdefault(n, []).append(dt)

    def median_ms(self, name: str) -> Optional[float]:
        d = self.durations.get(name)
        return statistics.median(d) * 1e3 if d else None


class Window:
    """The traced part of a window: profiler on, one ``bench_window``
    span around it. The profiler writes into a directory of this run's own
    under ``$TMPDIR``; ``trace`` is the reduced trace once it has ended."""

    def __init__(self, spans: Spans, on: bool):
        self.spans, self.on = spans, on
        self.trace: Optional[xplane.Trace] = None
        self._ann = None
        self._dir: Optional[str] = None

    def start(self):
        if not self.on:
            return
        import jax
        self._dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self._dir, profiler_options=opts)
        self._ann = jax.profiler.TraceAnnotation(xplane.WINDOW_SPAN)
        self._ann.__enter__()
        self.spans.tracing = True

    def stop(self):
        if not self.on or self._ann is None:
            return
        import jax
        self._ann.__exit__(None, None, None)
        self._ann = None
        self.spans.tracing = False
        jax.profiler.stop_trace()

    def reduce(self, span_names=()) -> Optional[xplane.Trace]:
        if self._dir is not None:
            self.trace = xplane.Trace(xplane.find_trace(self._dir),
                                      span_names)
            shutil.rmtree(self._dir, ignore_errors=True)
            self._dir = None
        return self.trace


@contextlib.contextmanager
def settled():
    """The window's host side without collections of set-up's objects:
    they are collected once and frozen before it, and thawed after it so
    that the program's state can be freed."""
    import gc
    gc.collect()
    gc.freeze()
    try:
        yield
    finally:
        gc.unfreeze()


def memory_peak_bytes() -> int:
    import jax
    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    return max(int(s.get("peak_bytes_in_use", 0)) for s in stats)


def per_layer(cell: spec.Cell, ctx: dict) -> Dict[str, dict]:
    """Each per-layer metric of the cell that its reader finds; a reader
    that finds nothing returns None and the metric is left out."""
    out = {}
    for m in cell.per_layer:
        value = spec.reader(m["name"])(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def result(cell: spec.Cell, trace: bool, e2e: dict, ctx: dict,
           checks: Dict[str, dict], *, attempted: int, failed: int,
           memory_peak_bytes: int) -> dict:
    """The run's result: end-to-end metrics, or with ``trace`` the
    per-layer ones, and each compared number beside its limit (a check
    passes when its value is at most its limit)."""
    correct = failed == 0 and all(c["value"] <= c["limit"]
                                  for c in checks.values())
    if trace:
        metrics = per_layer(cell, ctx)
    else:
        metrics = {m["name"]: {"value": float(e2e[m["name"]]),
                               "unit": m["unit"]}
                   for m in cell.end_to_end if e2e.get(m["name"]) is not None}
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics,
           "device": {"memory_peak_bytes": int(memory_peak_bytes)}}
    tr = ctx.get("trace")
    if trace and tr is not None:
        out["device"]["busy_s"] = tr.busy_s()
        out["device"]["window_s"] = tr.window_s
        out["breakdown"] = tr.breakdown()
    out["checks"] = checks
    return out
