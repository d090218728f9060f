"""Run one cell of the on-chip benchmark once.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

The cell's configuration, traffic mix, limits and per-layer metrics are
found by the names ``BENCHMARK.json`` gives them. Without a TPU, or with
fewer chips than the cell asks for, the run exits non-zero and prints no
result. The last line of standard output is the JSON result; the last
lines of standard error give each compared number beside its limit.
"""
from __future__ import annotations

import time

_T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

# the TPU runtime's own logs would go to a fixed directory outside the
# checkout; the run keeps none
os.environ.setdefault("TPU_LOG_DIR", "disabled")

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]

from benchmarks.chip import harness, spec  # noqa: E402


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool,
             t_start: float, device: dict, **kw) -> dict:
    result = spec.driver(cell.traffic["kind"])(cell, seed, seconds, trace,
                                                t_start, **kw)
    result["device"] = {**device, **result["device"]}
    checks = result.pop("checks")
    result["checks"] = checks          # the compared numbers come last
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    import repro.launch.compilation  # noqa: F401  (the system under test)
    device = spec.require_chips(cell.chips)
    harness.log(f"device: {device}; compile cache: "
                f"{harness.use_compile_cache()}")
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      _T_START, device)
    for name, c in result["checks"].items():
        harness.log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
