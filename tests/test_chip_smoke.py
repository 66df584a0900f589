"""chip_smoke.py's contract, as far as a CPU can check it, and the
compile-cache rule (paimon_tpu/ops/__init__.py).

The chip itself is only ever reached through the chip tool; here the
script runs as the explicit `--platform cpu` rehearsal, in a subprocess,
exactly as a builder would run it before spending chip time.
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SMOKE = os.path.join(_REPO, "chip_smoke.py")


def _run(args, cwd, env=None, script=_SMOKE, timeout=600):
    return subprocess.run([sys.executable, script, *args], cwd=str(cwd),
                          env=env, capture_output=True, text=True,
                          timeout=timeout)


def _env(**extra):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(extra)
    return env


@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    """One rehearsal for the whole module.  It inherits the suite's
    8-device CPU mesh, so the mesh route has a lane to place on each of
    eight devices — the sandbox's stand-in for a four-chip host."""
    cwd = tmp_path_factory.mktemp("smoke")
    proc = _run(["--platform", "cpu", "--rows", "200000"], cwd, _env())
    return cwd, proc


def test_rehearsal_passes_and_reports(rehearsal):
    cwd, proc = rehearsal
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    tag = "[chip_smoke] report "
    assert lines[-2].startswith(tag), lines[-2][:80]
    out = json.loads(lines[-2][len(tag):])
    # the contract's result line: the last one, these keys and no other
    last = json.loads(lines[-1])
    assert list(last) == ["ok", "device"] and last["ok"] is True
    assert list(last["device"]) == ["platform", "kind", "count"]
    assert last["device"] == out["device"] == {
        "platform": "cpu", "kind": "cpu", "count": out["local_devices"]}
    # the report before it
    assert out["ok"] is True and out["rehearsal"] is True
    assert out["claim"] is None
    # header
    for key in ("jax", "backend", "compile_cache_dir", "native_library",
                "rows", "rows_default", "seed"):
        assert key in out, key
    assert out["backend"] == "cpu" and out["rows"] == 200000
    assert os.path.basename(out["native_library"]).startswith(
        "_paimon_native-")
    # both phases, with the path and compile counters
    a, b = out["phase_a"], out["phase_b"]
    assert a["rows"] == 200000 and a["lookups"] == 1000
    assert a["native_fallbacks"] == 0 and a["native_probes"] > 0
    assert b["rows"] == 50000
    for phase in (a, b):
        for key in ("backend_compiles", "backend_compile_s",
                    "persistent_cache_hits"):
            assert key in phase, key
    assert {v["variant"] for v in b["variants"]} == {
        "dedup.packed", "agg.full-perm", "dedup.mesh",
        "agg.mesh", "dedup.device-decode", "agg.device-decode"}
    assert all(b["programs_built"].values()), b["programs_built"]
    # the mesh route sized itself from the devices it found
    n_dev = out["local_devices"]
    assert b["buckets"] == max(8, 2 * n_dev)
    assert b["mesh_devices"] == list(range(n_dev))
    for v in b["variants"]:
        if "mesh" in v:
            assert v["mesh"]["retries"] == v["mesh"]["fallbacks"] == 0
            assert len(v["mesh"]["lane_rows"]) == n_dev
            assert all(r > 0 for r in v["mesh"]["lane_rows"])
    steps = {s["step"]: s for s in out["steps"]}
    assert steps["A.compact second pass"]["backend_compiles"] == 0
    for s in out["steps"]:
        assert set(s["merge_paths"]) == {"host", "device", "ovc"}
    # on a CPU backend the router keeps phase A on the host, and says so
    assert out["default_route_device_merges"] == 0
    # the same report went to the output directory, under the cwd
    with open(os.path.join(cwd, "chiprun_out", "chip_smoke.json")) as f:
        assert json.load(f) == out


def test_refuses_a_machine_without_a_chip(tmp_path):
    """No flag, no accelerator: non-zero exit, the backend named, no
    result printed."""
    proc = _run([], tmp_path, _env(JAX_PLATFORMS="cpu"), timeout=120)
    assert proc.returncode not in (0, None)
    assert "'cpu'" in proc.stderr and "tpu" in proc.stderr
    assert "{" not in proc.stdout
    assert not os.path.exists(tmp_path / "chiprun_out")


def test_refuses_to_run_without_the_program(tmp_path):
    """In a directory that holds chip_smoke.py and nothing else of the
    repo it fails, rehearsal flag or not, and prints no result."""
    alone = shutil.copy(_SMOKE, tmp_path / "chip_smoke.py")
    proc = _run(["--platform", "cpu", "--rows", "1000"], tmp_path, _env(),
                script=alone, timeout=120)
    assert proc.returncode not in (0, None)
    assert "paimon_tpu" in proc.stderr
    assert "{" not in proc.stdout


# -- the compile-cache rule --------------------------------------------------

_PRINT_CACHE = ("import paimon_tpu.ops, jax; "
                "print(jax.config.jax_compilation_cache_dir)")


def _cache_dir_in_fresh_interpreter(**env_extra):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(env_extra)
    proc = subprocess.run([sys.executable, "-c", _PRINT_CACHE], cwd=_REPO,
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return proc.stdout.strip().splitlines()[-1]


def test_cache_defaults_to_the_checkout_and_never_moves():
    first = _cache_dir_in_fresh_interpreter()
    second = _cache_dir_in_fresh_interpreter()
    assert first == second == os.path.join(_REPO, ".jax_cache")


def test_cache_variable_wins_and_is_left_alone(tmp_path):
    placed = str(tmp_path / "some" / "dir")
    assert _cache_dir_in_fresh_interpreter(
        JAX_COMPILATION_CACHE_DIR=placed) == placed


def test_no_code_builds_a_cache_path_from_a_moving_name():
    """One place sets the cache; nothing else in the tree does."""
    hits = []
    for root in ("paimon_tpu", "benchmarks", "tests"):
        for dirpath, _, names in os.walk(os.path.join(_REPO, root)):
            for name in names:
                if name.endswith(".py"):
                    hits.append(os.path.join(dirpath, name))
    hits += [os.path.join(_REPO, n)
             for n in ("bench.py", "__graft_entry__.py", "chip_smoke.py")]
    setters = []
    for path in hits:
        if os.path.abspath(path) == os.path.abspath(__file__):
            continue
        with open(path) as f:
            text = f.read()
        if "jax_compilation_cache_dir\"," in text \
                or "JAX_COMPILATION_CACHE_DIR\"," in text \
                or "JAX_COMPILATION_CACHE_DIR\"]" in text:
            setters.append(os.path.relpath(path, _REPO))
    assert setters == [os.path.join("paimon_tpu", "ops", "__init__.py")]
