"""BENCHMARK.json against the contract's rules, before any chip call.

A manifest outside these limits is refused before a single run (PR 22
was: one `layer` with a space in it), so every rule the contract states
about the file's form is a case here.
"""

import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
TRAFFIC_SUFFIXES = (".json", ".jsonl", ".toml", ".txt", ".csv")
WIDTH_WORDS = ("hidden", "intermediate", "latent", "state", "projection",
               "head", "expansion", "experts_per")


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _line(text, what):
    assert isinstance(text, str) and 1 <= len(text) <= 200, what
    assert "\n" not in text and "\t" not in text, what


def _cells_of(metric, manifest):
    return metric.get("workloads") or [w["name"]
                                       for w in manifest["workloads"]]


def test_top_level_keys_and_size(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 << 10


def test_command_and_paths(manifest):
    paths = manifest["paths"]
    assert 1 <= len(paths) <= 16
    for p in paths:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert os.path.isdir(os.path.join(ROOT, p))
    cmd = manifest["command"]
    assert 1 <= len(cmd) <= 32
    for word in cmd:
        _line(word, word)
        assert not word.startswith("/") and ".." not in word.split("/")
        if os.path.exists(os.path.join(ROOT, word)):    # a file of the repo
            assert any(word == p or word.startswith(p + "/") for p in paths)


def test_run_seconds_fits_the_full_check(manifest):
    rs = manifest["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    cells = 24                          # later PRs may fill every slot
    assert (2 + 14 * cells) * (rs + 60) + cells * 2 * 90 + 1200 <= 43200


def test_configs(manifest):
    configs = manifest["configs"]
    assert 1 <= len(configs) <= 24
    names = [c["name"] for c in configs]
    files = [c["file"] for c in configs]
    assert len(set(names)) == len(names) and len(set(files)) == len(files)
    used = {w["config"] for w in manifest["workloads"]}
    for c in configs:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        _line(c["source"], "source")
        _line(c["why"], "why")
        assert PATH.match(c["file"])
        assert any(c["file"].startswith(p + "/") for p in manifest["paths"])
        with open(os.path.join(ROOT, c["file"])) as f:
            body = json.load(f)
        assert isinstance(body, dict) and body["source"] == c["source"]
        assert len(c["reduced"]) <= 16
        for key in c["reduced"]:
            assert NAME.match(key)
            assert not key.endswith(("_dim", "_rank"))
            assert not any(w in key for w in WIDTH_WORDS)
            assert key in body["reduced"], "every cut is explained in the file"
        assert set(body["reduced"]) == set(c["reduced"])
        assert body["guarantees"], "the file states what `correct` holds"


def test_workloads(manifest):
    cells = manifest["workloads"]
    assert 1 <= len(cells) <= 24
    names = [w["name"] for w in cells]
    pairs = [(w["config"], w["traffic"]) for w in cells]
    assert len(set(names)) == len(names) and len(set(pairs)) == len(pairs)
    configs = {c["name"] for c in manifest["configs"]}
    four = 0
    for w in cells:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs
        assert w["chips"] in (1, 4)
        four += w["chips"] == 4
        _line(w["why"], "why")
        traffic = [f for f in os.listdir(os.path.join(ROOT, "chipbench",
                                                      "traffic"))
                   if os.path.splitext(f)[0] == w["traffic"]]
        assert len(traffic) == 1 and traffic[0].endswith(TRAFFIC_SUFFIXES)
        with open(os.path.join(ROOT, "chipbench", "traffic",
                               traffic[0])) as f:
            kind = json.load(f)["operation"]
        assert os.path.isfile(os.path.join(ROOT, "chipbench", "operations",
                                           kind + ".py"))
    assert four == 0, "ISSUE 24: no four-chip cell now"
    assert four <= max(1, len(cells) // 2)


def _metric_form(m, keys, manifest):
    assert set(m) - {"workloads"} == keys, m
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher")
    assert m["source"] in SOURCES
    cells = {w["name"] for w in manifest["workloads"]}
    if "workloads" in m:
        assert m["workloads"] and set(m["workloads"]) <= cells
    with open(os.path.join(ROOT, "chipbench", "metrics",
                           m["name"] + ".json")) as f:
        reader = json.load(f)["reader"]
    assert os.path.isfile(os.path.join(ROOT, "chipbench", "readers",
                                       reader + ".py"))


def test_end_to_end(manifest):
    metrics = manifest["end_to_end"]
    assert 1 <= len(metrics) <= 16
    by_name = {m["name"]: m for m in metrics}
    assert len(by_name) == len(metrics)
    assert "setup_s" in by_name and "workloads" not in by_name["setup_s"]
    assert len(metrics) - 1 <= 4, "ISSUE 24: at most four besides setup_s"
    for m in metrics:
        _metric_form(m, {"name", "unit", "better", "bound", "source"},
                     manifest)
        assert m["source"] in ("host_clock", "device_trace")
        assert isinstance(m["bound"], float) and 0.01 <= m["bound"] <= 0.25
    for w in manifest["workloads"]:
        reported = {m["name"] for m in metrics
                    if w["name"] in _cells_of(m, manifest)}
        assert "setup_s" in reported and len(reported) >= 2, w["name"]


def test_per_layer(manifest):
    metrics = manifest["per_layer"]
    assert 1 <= len(metrics) <= 128
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    names = [m["name"] for m in metrics]
    assert len(set(names)) == len(names) and not set(names) & set(e2e)
    for m in metrics:
        _metric_form(m, {"name", "unit", "better", "source", "layer",
                         "moves"}, manifest)
        # the rule that refused PR 22: a layer is a name, without spaces
        assert NAME.match(m["layer"]), m["layer"]
        assert m["moves"] in e2e
        moved = set(_cells_of(e2e[m["moves"]], manifest))
        assert set(_cells_of(m, manifest)) <= moved, m["name"]
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
    for w in manifest["workloads"]:
        assert any(w["name"] in _cells_of(m, manifest) for m in metrics)


def test_files_under_paths_are_named_from_name_characters(manifest):
    for p in manifest["paths"]:
        for d, _, files in os.walk(os.path.join(ROOT, p)):
            if "__pycache__" in d or ".pytest_cache" in d:
                continue
            for f in files:
                rel = os.path.relpath(os.path.join(d, f), ROOT)
                assert PATH.match(rel), rel
