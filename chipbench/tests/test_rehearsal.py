"""Each cell end to end at a tiny size on the CPU, through the command
the driver runs."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
CELLS = [w["name"] for w in MANIFEST["workloads"]]


def _run(*extra, env=None, cell=CELLS[0], trace="0"):
    full_env = {**os.environ, "JAX_PLATFORMS": "cpu", **(env or {})}
    return subprocess.run(
        [sys.executable, *MANIFEST["command"][1:], "--workload", cell,
         "--seed", "3000000019", "--seconds", "1", "--trace", trace, *extra],
        cwd=ROOT, env=full_env, capture_output=True, text=True, timeout=600)


@pytest.mark.parametrize("trace", ["0", "1"])
@pytest.mark.parametrize("cell", CELLS)
def test_cell_rehearses_and_prints_no_measurement(cell, trace):
    done = _run("--rehearsal", cell=cell, trace=trace)
    assert done.returncode == 0, done.stderr[-2000:]
    lines = done.stdout.strip().splitlines()
    assert lines[-2].startswith("[chipbench] info ")
    result = json.loads(lines[-1])
    assert set(result) == {"correct", "attempted", "failed", "metrics",
                           "device"}
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 1
    assert result["device"]["platform"] == "cpu"
    assert result["metrics"] == {}      # a CPU number is never a metric
    info = json.loads(lines[-2][len("[chipbench] info "):])
    assert info["window_compiles"]["backend_compiles"] == 0


def test_without_a_chip_the_command_fails_and_prints_no_result():
    done = _run()
    assert done.returncode != 0
    assert "{" not in done.stdout
    assert "needs 1 TPU chip" in done.stderr


def test_unknown_cell_fails():
    done = _run("--rehearsal", cell="no_such_cell")
    assert done.returncode != 0 and "{" not in done.stdout


def test_no_window_opens_with_a_pinned_merge_route():
    done = _run("--rehearsal", env={"PAIMON_FORCE_DEVICE_SORT": "1"})
    assert done.returncode != 0
    assert "{" not in done.stdout
    assert "PAIMON_FORCE_DEVICE_SORT" in done.stderr
