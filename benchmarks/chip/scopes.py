"""Device ops of a traced window put down to the program's scopes.

On a TPU each ``XLA Ops`` event of the trace is named by the text of one
HLO instruction (``%fusion.253 = bf16[16,13824]{...} fusion(...)``). Its
scope, the ``jax.named_scope`` stack that made it (``.../layer/moe/
experts/mlp/dot_general``), is read from one of two places:

* the event's framework-op stat, where the trace carries one;
* otherwise the ``op_name`` metadata of the instruction of that name and
  result type in the compiled HLO text of the step, which
  ``drivers/serve_mla_moe.py`` takes after the window.

``ScopedTrace`` is ``xplane.Trace`` with that mapping: ``scope_time_s``
sums the device time of the ops under a scope, and ``breakdown`` adds the
time by scope. A trace with nothing to map (no device ops, or neither
source) reads nothing for any scope, so a metric built on it is left out.
"""
from __future__ import annotations

import gzip
import re
from typing import Dict, Optional, Sequence, Tuple

from benchmarks.chip import xplane

# the scopes the program's MLA and MoE layers open in the decode step;
# literals of the benchmark's own, so that a renamed scope shows as a
# reading gone missing
SCOPES = ("mla/absorb", "mla/attend", "mla/write",
          "moe/router", "moe/experts", "moe/shared")
# event stats that name the framework op, by the names profilers use
_FRAMEWORK_STATS = ("tf_op", "framework_op_name", "long_name")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?(%?[\w.\-]+) = (\S+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def hlo_scopes(text: str) -> Dict[Tuple[str, Optional[str]], str]:
    """``(instruction name, result type) -> op_name`` of every instruction
    of an HLO module's text that carries one, and ``(name, None) ->
    op_name`` for each name that one instruction alone has."""
    out: Dict[Tuple[str, Optional[str]], str] = {}
    seen: Dict[str, int] = {}
    for line in text.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        name = m.group(1).lstrip("%")
        seen[name] = seen.get(name, 0) + 1
        op = _OP_NAME.search(line)
        if op:
            out[(name, m.group(2))] = op.group(1)
            out[(name, None)] = op.group(1)
    return {k: v for k, v in out.items()
            if k[1] is not None or seen[k[0]] == 1}


def _key(event_name: str) -> Optional[Tuple[str, str]]:
    m = _INSTR.match(event_name)
    return (m.group(1).lstrip("%"), m.group(2)) if m else None


def in_scope(op_name: str, scope: str) -> bool:
    """Whether ``scope`` (``moe`` or ``moe/experts``) lies on the op's
    name stack, as whole segments."""
    return f"/{scope}/" in f"/{op_name}/"


class ScopedTrace(xplane.Trace):
    """``xplane.Trace`` with each device op's scope: from the events'
    framework-op stat where there is one, else from ``hlo_text``.
    ``source`` says which: "trace", "hlo" or None."""

    def __init__(self, path: str, span_names: Sequence[str] = (),
                 hlo_text: Optional[str] = None):
        super().__init__(path, span_names)
        self.scope_of: Dict[str, str] = {}
        self.source: Optional[str] = None
        stats = self._framework_ops(path)
        if stats:
            self.scope_of, self.source = stats, "trace"
        elif hlo_text:
            table = hlo_scopes(hlo_text)
            for ops in self.ops.values():
                for _, _, name in ops:
                    k = _key(name)
                    op = None if k is None else table.get(
                        k, table.get((k[0], None)))
                    if op is not None:
                        self.scope_of[name] = op
            self.source = "hlo" if self.scope_of else None

    @staticmethod
    def _framework_ops(path: str) -> Dict[str, str]:
        import jax
        if path.endswith(".gz"):
            with gzip.open(path) as f:
                data = jax.profiler.ProfileData.from_serialized_xspace(
                    f.read())
        else:
            data = jax.profiler.ProfileData.from_file(path)
        out = {}
        for plane in data.planes:
            if not xplane._DEVICE_PLANE.match(plane.name):
                continue
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for ev in line.events:
                    for k, v in ev.stats:
                        if k in _FRAMEWORK_STATS and isinstance(v, str) \
                                and "/" in v:
                            out[ev.name] = v
        return out

    def scope_time_s(self, scope: str) -> Optional[float]:
        """Device seconds of the ops under ``scope`` in the window, or None
        where no op could be put down to any scope."""
        if not self.scope_of:
            return None
        total = 0.0
        for ops in self.ops.values():
            for s, e, name in ops:
                op = self.scope_of.get(name)
                if op is not None and in_scope(op, scope) and \
                        xplane.op_key(name) not in xplane._CONTAINERS:
                    total += (e - s) * 1e-9
        return total

    def breakdown(self, k: int = 10) -> dict:
        out = super().breakdown(k)
        if self.scope_of:
            times = {s: self.scope_time_s(s) for s in SCOPES}
            busy = sum(op for op in self.op_time_s().values())
            times["other"] = busy - sum(times.values())
            out["device_scopes"] = [[n, t] for n, t in sorted(
                times.items(), key=lambda kv: -kv[1])]
            out["scope_source"] = self.source
        return out


def roofline(ctx: dict, scope: str, least_bytes) -> Optional[float]:
    """Percent of its bandwidth roofline that the ops under ``scope``
    reached: ``least_bytes`` (what the traced ticks had to move there, at
    least) over the chip's HBM bandwidth, against their device time. None
    where the trace maps no op to a scope or has no bytes to count."""
    from benchmarks.chip import peaks
    tr = ctx.get("trace")
    if not least_bytes or not isinstance(tr, ScopedTrace):
        return None
    seconds = tr.scope_time_s(scope)
    if not seconds:
        return None
    least = least_bytes / peaks.lookup(ctx["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * least / seconds
