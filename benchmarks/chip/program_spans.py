"""The serving engine's own spans in a traced window, and what they say of
the device's idle time.

The program writes one ``raptor/engine.step`` tree a tick into the
profiler's trace, on the device's clock, with its counts as event stats.
``ProgramTrace`` is ``xplane.Trace`` with those spans kept besides: each
with its stats in ``program``, and joined into ``spans`` so that an idle
gap is named by the innermost program span that holds it. No cell reads it
yet (the harness reduces its trace with ``xplane.Trace``, which keeps only
its own spans). Run it on a saved trace with a ``bench_window`` span
around the ticks to read::

    python -m benchmarks.chip.program_spans <trace.xplane.pb[.gz]>

It prints, as JSON, the per-tick readings below and the idle gaps.
"""
from __future__ import annotations

import gzip
import json
import statistics
import sys
from typing import Dict, List, Optional, Sequence, Tuple

from benchmarks.chip import xplane

# a literal of the benchmark's own, not imported from the program: a span
# renamed in the program shows as a reading gone missing
PREFIX = "raptor/"
STEP = PREFIX + "engine.step"

Interval = Tuple[float, float]


def intersect(a: Sequence[Interval], b: Sequence[Interval]) -> float:
    """Length of the overlap of two sets of disjoint intervals."""
    return sum(max(0.0, min(e1, e2) - max(s1, s2))
               for s1, e1 in a for s2, e2 in b)


class ProgramTrace(xplane.Trace):
    """``xplane.Trace`` and the program's spans: ``program`` holds each as
    ``(start, end, name, stats)``."""

    def __init__(self, path: str, span_names: Sequence[str] = ()):
        super().__init__(path, span_names)
        import jax
        if path.endswith(".gz"):
            with gzip.open(path) as f:
                data = jax.profiler.ProfileData.from_serialized_xspace(
                    f.read())
        else:
            data = jax.profiler.ProfileData.from_file(path)
        self.program: List[Tuple[float, float, str, dict]] = [
            (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name,
             {k: v for k, v in ev.stats if not k.startswith("_")})
            for plane in data.planes if plane.name == "/host:CPU"
            for line in plane.lines for ev in line.events
            if ev.name.startswith(PREFIX)]
        self.spans += [p[:3] for p in self.program]

    def trees(self, root: str = STEP) -> List[Tuple[tuple, List[tuple]]]:
        """Each program span named ``root`` inside the window, with the
        program spans it holds."""
        return [(r, [c for c in self.program
                     if c is not r and r[0] <= c[0] and c[1] <= r[1]])
                for r in self.program
                if r[2] == root and self.t0 <= r[0] and r[1] <= self.t1]


def _within(children, name: str) -> List[Interval]:
    return [(c[0], c[1]) for c in children if c[2] == PREFIX + name]


def _median(values: List[float]) -> Optional[float]:
    return statistics.median(values) if values else None


def _tick_idle_ms(tr: ProgramTrace, part) -> Optional[float]:
    """Median over ticks of the device's idle time inside the intervals
    that ``part(tick, children)`` gives, in ms; None without chip ops."""
    ticks = tr.trees() if tr.ops else []
    idle = [(s, e) for s, e, _ in tr.idle_gaps()]
    return _median([intersect(idle, part(t, c)) * 1e-6 for t, c in ticks])


def wait_idle_ms(tr: ProgramTrace) -> Optional[float]:
    """Per tick, device idle inside ``engine.wait`` (completion latency)."""
    return _tick_idle_ms(tr, lambda t, c: _within(c, "engine.wait"))


def host_idle_ms(tr: ProgramTrace) -> Optional[float]:
    """Per tick, device idle inside ``engine.step`` and outside
    ``engine.wait`` (the host at work)."""
    def outside_wait(t, c):
        parts, cur = [], t[0]
        for s, e in sorted(_within(c, "engine.wait")):
            parts.append((cur, s))
            cur = e
        return parts + [(cur, t[1])]
    return _tick_idle_ms(tr, outside_wait)


def span_ms(tr: ProgramTrace, *names: str) -> Optional[float]:
    """Median, over the ticks that hold any of the spans ``names``, of the
    host time each spent in them, in ms."""
    per_tick = []
    for _, children in tr.trees():
        spans = [iv for n in names for iv in _within(children, n)]
        if spans:
            per_tick.append(sum(e - s for s, e in spans) * 1e-6)
    return _median(per_tick)


def emit_share(tr: ProgramTrace) -> Optional[float]:
    """Percent of the ticks' live slots that emitted a token: ``emitted``
    of ``engine.emit`` over ``live`` of ``engine.dispatch``."""
    emitted = live = 0
    for _, children in tr.trees():
        for c in children:
            if c[2] == PREFIX + "engine.emit":
                emitted += c[3].get("emitted", 0)
            elif c[2] == PREFIX + "engine.dispatch":
                live += c[3].get("live", 0)
    return 100.0 * emitted / live if live else None


def summary(tr: ProgramTrace) -> Dict[str, object]:
    return {"ticks": len(tr.trees()),
            "wait_idle_ms": wait_idle_ms(tr),
            "host_idle_ms": host_idle_ms(tr),
            "logits_fetch_ms": span_ms(tr, "engine.fetch"),
            "shadow_observe_ms": span_ms(tr, "shadow.observe",
                                         "shadow.check"),
            "emit_share": emit_share(tr),
            "idle_gaps": tr.breakdown()["idle_gaps"]}


if __name__ == "__main__":
    print(json.dumps(summary(ProgramTrace(sys.argv[1])), indent=1))
