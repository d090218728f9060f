"""CPU rehearsal of every cell: its traffic, drivers and metric code run
end to end at a small size, and the result line has the keys the benchmark
promises. The command itself refuses to run without a TPU."""
import json
import os
import subprocess
import sys

import pytest

from conftest import ROOT

CELLS = [w["name"] for w in json.load(
    open(os.path.join(ROOT, "BENCHMARK.json")))["workloads"]]
KEYS = ["correct", "attempted", "failed", "metrics", "device"]


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("trace", [False, True])
def test_cell_rehearses_on_cpu(small_cell, run_small, name, trace):
    cell = small_cell(name)
    res = run_small(cell, trace=trace)
    assert list(res)[:5] == KEYS and list(res)[-1] == "checks"
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["device"]["platform"] == "cpu"
    want = cell.per_layer if trace else cell.end_to_end
    names = {m["name"] for m in want}
    assert set(res["metrics"]) <= names
    if not trace:
        assert set(res["metrics"]) == names
    else:
        # no device ops on the CPU: only the host spans can be read, and
        # no share of a roofline or a peak is reported as 0
        assert res["device"]["window_s"] > 0
        assert all(m["value"] > 0 for m in res["metrics"].values())
        # the host-clock metrics are there, the device ones are not
        host = {m["name"] for m in want if m["source"] == "host_clock"}
        assert set(res["metrics"]) == host
    for c in res["checks"].values():
        assert set(c) == {"value", "limit"}


def test_command_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_command_fails_without_the_program(tmp_path):
    """A checkout that holds only BENCHMARK.json and the benchmark's own
    files has no system to measure."""
    import shutil
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmarks", "chip"),
                    tmp_path / "benchmarks" / "chip")
    proc = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0 and proc.stdout.strip() == ""
    assert "No module named 'repro'" in proc.stderr
