"""Reduction of a profiler trace (``*.xplane.pb``) to the benchmark's
device numbers, with nothing but JAX's own reader.

On a TPU the trace holds one plane per chip (``/device:TPU:<n>``) whose
``XLA Ops`` line has one event per executed HLO instruction, named by the
instruction's text (shapes included), and a ``/host:CPU`` plane whose
lines hold the harness's ``TraceAnnotation`` spans. Both planes share one
clock. Busy time is the union of op intervals inside the traced window,
so ops that overlap count once.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW_SPAN = "bench_window"
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_SUFFIX = re.compile(r"\.\d+$")
# ops whose events span the ops of their bodies: counted in busy time
# through those, never as ops of their own
_CONTAINERS = frozenset({"while", "conditional", "call"})


def find_trace(log_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def op_key(name: str) -> str:
    """``%quantize_em.50 = f32[...] custom-call(...)`` -> ``quantize_em``."""
    head = name.split(" = ")[0].strip().lstrip("%")
    return _SUFFIX.sub("", head)


def _union(intervals: Sequence[Tuple[float, float]]
           ) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


class Trace:
    """Device ops and harness spans of one trace, clipped to the window
    that the harness's ``bench_window`` span marks."""

    def __init__(self, path: str, span_names: Sequence[str] = ()):
        import gzip
        import jax
        if path.endswith(".gz"):
            with gzip.open(path) as f:
                data = jax.profiler.ProfileData.from_serialized_xspace(
                    f.read())
        else:
            data = jax.profiler.ProfileData.from_file(path)
        self.ops: Dict[int, List[Tuple[float, float, str]]] = {}
        self.spans: List[Tuple[float, float, str]] = []
        wanted = set(span_names) | {WINDOW_SPAN}
        for plane in data.planes:
            m = _DEVICE_PLANE.match(plane.name)
            if m:
                ops = self.ops.setdefault(int(m.group(1)), [])
                for line in plane.lines:
                    if line.name != "XLA Ops":
                        continue
                    for ev in line.events:
                        ops.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                    ev.name))
            elif plane.name == "/host:CPU":
                for line in plane.lines:
                    for ev in line.events:
                        if ev.name in wanted:
                            self.spans.append(
                                (ev.start_ns, ev.start_ns + ev.duration_ns,
                                 ev.name))
        windows = [s for s in self.spans if s[2] == WINDOW_SPAN]
        if not windows:
            raise ValueError(f"trace {path} has no {WINDOW_SPAN!r} span")
        self.t0, self.t1 = windows[0][0], windows[0][1]
        for chip, ops in self.ops.items():
            self.ops[chip] = [(max(s, self.t0), min(e, self.t1), n)
                              for s, e, n in ops
                              if e > self.t0 and s < self.t1]

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) * 1e-9

    def busy_s(self) -> float:
        """Seconds in which some op ran, averaged over the chips traced."""
        if not self.ops:
            return 0.0
        per_chip = [sum(e - s for s, e in _union([(s, e) for s, e, _ in ops]))
                    for ops in self.ops.values()]
        return sum(per_chip) / len(per_chip) * 1e-9

    def op_time_s(self) -> Dict[str, float]:
        """Device seconds by op name (numeric suffix dropped), all chips."""
        out: Dict[str, float] = {}
        for ops in self.ops.values():
            for s, e, n in ops:
                k = op_key(n)
                if k not in _CONTAINERS:
                    out[k] = out.get(k, 0.0) + (e - s) * 1e-9
        return out

    def events(self, prefix: str) -> List[Tuple[float, str]]:
        """``(seconds, instruction text)`` of every op whose name starts
        with ``prefix`` (``quantize_em`` finds both quantize kernels)."""
        return [((e - s) * 1e-9, n) for ops in self.ops.values()
                for s, e, n in ops if op_key(n).startswith(prefix)]

    def idle_gaps(self, chip: Optional[int] = None
                  ) -> List[Tuple[float, float, str]]:
        """Idle intervals of one chip in the window, each named by the
        innermost harness span that holds its middle."""
        if not self.ops:
            return []
        chip = min(self.ops) if chip is None else chip
        busy = _union([(s, e) for s, e, _ in self.ops[chip]])
        gaps, cur = [], self.t0
        for s, e in busy:
            if s > cur:
                gaps.append((cur, s))
            cur = max(cur, e)
        if cur < self.t1:
            gaps.append((cur, self.t1))
        out = []
        for s, e in gaps:
            mid = (s + e) / 2
            inner = [sp for sp in self.spans if sp[2] != WINDOW_SPAN
                     and sp[0] <= mid <= sp[1]]
            name = min(inner, key=lambda sp: sp[1] - sp[0])[2] if inner \
                else "between harness spans"
            out.append((s, e, name))
        return out

    def breakdown(self, k: int = 10) -> dict:
        ops = sorted(self.op_time_s().items(), key=lambda kv: -kv[1])[:k]
        gaps = sorted(self.idle_gaps(), key=lambda g: -(g[1] - g[0]))[:k]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[n, (e - s) * 1e-9] for s, e, n in gaps]}
