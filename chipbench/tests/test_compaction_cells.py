"""Every cell whose traffic is a kind of compaction runs its operations
under the program's `compact.task` root and — where its table has a
sequence group — leaves `agg.select` in its rehearsal trace: the
`[chipbench] spans` line, from the reduction's own command.

`test_span_reduce.py` keys the root it expects by the name of a cell and
knows the first three; the cells found here are found by their traffic."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


MANIFEST = _load("BENCHMARK.json")
COMPACTIONS = [w for w in MANIFEST["workloads"]
               if _load("chipbench", "traffic", w["traffic"] + ".json")
               ["operation"].startswith("compact")]


@pytest.mark.parametrize("cell", COMPACTIONS, ids=lambda w: w["name"])
def test_a_rehearsal_trace_holds_the_compactions_spans(cell):
    seed = "3000000029"
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    done = subprocess.run(
        [sys.executable, *MANIFEST["command"][1:], "--workload",
         cell["name"], "--seed", seed, "--seconds", "1", "--trace", "1",
         "--rehearsal"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    assert json.loads(done.stdout.strip().splitlines()[-1])["correct"]
    traces = os.path.join(ROOT, "chiprun_out", "chipbench", "traces",
                          f"{cell['name']}.seed{seed}")
    done = subprocess.run(
        [sys.executable, "-m", "chipbench.span_reduce", traces],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert done.returncode == 0, done.stderr[-2000:]
    line = done.stdout.strip().splitlines()[-1]
    body = json.loads(line[len("[chipbench] spans "):])
    assert body["device"] is False and body["spans"] > 0
    assert "compact.task" in body["self_ms"]
    assert "merge.gather" in body["idle_s_by_leaf"]
    config = next(c for c in MANIFEST["configs"]
                  if c["name"] == cell["config"])
    grouped = any(k.endswith(".sequence-group") for k in
                  _load(config["file"])["table"]["options"])
    assert ("agg.select" in body["self_ms"]) == grouped
