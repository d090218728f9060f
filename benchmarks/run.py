# One function per paper table/figure. Prints ``name,us_per_call,derived``
# CSV blocks (plus per-benchmark headers) and writes a machine-readable
# ``BENCH_<name>.json`` artifact per benchmark (us_per_call + derived
# metrics + wall time) into $BENCH_OUT (default: cwd) so the perf
# trajectory is tracked across PRs. ``python -m benchmarks.run``.
#
# A raising benchmark is recorded, the remaining benchmarks still run (their
# artifacts stay comparable), no artifact is written for the failed one, and
# the process exits nonzero — so a CI bench job can never upload partial
# artifacts and still pass.
from __future__ import annotations

import json
import os
import platform
import sys
import time
import traceback


def _write_artifact(out_dir: str, name: str, wall_s: float, rows) -> str:
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"BENCH_{name}.json")
    payload = {
        "benchmark": name,
        "wall_s": round(wall_s, 3),
        "rows": rows,
        "meta": {
            "python": platform.python_version(),
            "platform": platform.platform(),
        },
    }
    try:
        import jax
        payload["meta"]["jax"] = jax.__version__
        payload["meta"]["backend"] = jax.default_backend()
    except Exception:
        pass
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
        f.write("\n")
    return path


def run_benches(benches, only: str | None, out_dir: str) -> list:
    """Run the selected benchmarks, writing one artifact per SUCCESS.
    Returns the list of (name, exception) failures instead of dying on the
    first one, so a broken benchmark can't silently skip the rest while the
    survivors' artifacts still upload."""
    from benchmarks import common

    failures = []
    for name, fn in benches:
        if only and only not in name:
            continue
        print(f"\n===== {name} =====", flush=True)
        common.reset_results()
        t0 = time.time()
        try:
            fn()
        except Exception as e:  # noqa: BLE001 — report and keep going
            traceback.print_exc()
            print(f"# {name} FAILED after {time.time() - t0:.1f}s: {e!r}",
                  flush=True)
            failures.append((name, e))
            continue
        wall = time.time() - t0
        path = _write_artifact(out_dir, name, wall, list(common.RESULTS))
        print(f"# {name} done in {wall:.1f}s -> {path}", flush=True)
    return failures


def main() -> int:
    from repro.launch.compilation import use_compile_cache
    use_compile_cache()
    from benchmarks import (
        fig7_truncation_sweep, table2_memmode, table3_overhead,
        fig8_speedup_model, kernels_micro, perf_fp8_dot, roofline_table,
        search_convergence, apps_e2e, instability_profile,
        serving_throughput, static_prune,
    )
    benches = [
        ("apps_e2e", apps_e2e.run),
        ("instability_profile", instability_profile.run),
        ("serving_throughput", serving_throughput.run),
        ("static_prune", static_prune.run),
        ("fig7_truncation_sweep", fig7_truncation_sweep.run),
        ("table2_memmode", table2_memmode.run),
        ("table3_overhead", table3_overhead.run),
        ("fig8_speedup_model", fig8_speedup_model.run),
        ("kernels_micro", kernels_micro.run),
        ("perf_fp8_dot", perf_fp8_dot.run),
        ("roofline_table", roofline_table.run),
        ("search_convergence", search_convergence.run),
        ("search_sharded", search_convergence.run_sharded),
    ]
    only = sys.argv[1] if len(sys.argv) > 1 else None
    out_dir = os.environ.get("BENCH_OUT", ".")
    failures = run_benches(benches, only, out_dir)
    if failures:
        names = ", ".join(n for n, _ in failures)
        print(f"\n# {len(failures)} benchmark(s) FAILED: {names}",
              file=sys.stderr, flush=True)
        return 1
    return 0


if __name__ == '__main__':
    sys.exit(main())
