"""The control's readings, which set the upper end of a cell's limits,
on the chip.

    python3 benchmarks/chip/control.py --workload <cell> --seconds <s> \
        --seeds <n> [<n> ...]

For each seed, in one process: a run of the cell whose comparison takes
the control, the reference computed in fp8, in the program's place (the
tokens it puts first at the served positions, or its loss of each sampled
batch). Every such run must read ``correct: false``. Prints one JSON line
per seed with ``correct`` and each compared number beside its limit. The
benchmark's own runs never run the control; the program's readings are
those of its ordinary runs.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]

from benchmarks.chip import harness, spec  # noqa: E402
from benchmarks.chip.run import run_cell  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    device = spec.require_chips(cell.chips)
    harness.use_compile_cache()
    for seed in args.seeds:
        res = run_cell(cell, seed, args.seconds, False, time.time(), device,
                       control=True)
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "correct": res["correct"],
                          "metrics": res["metrics"],
                          "checks": res["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
