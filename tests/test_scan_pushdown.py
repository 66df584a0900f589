"""`to_arrow(projection, predicate)` on `mor50m-dedup`'s rehearsal size
equals the masked numpy reference (the `dedup_scan_pushdown` cell's
operation and its traffic file, on the CPU)."""

import json
import os
import types

import numpy as np
import pytest

from chipbench import data, reference
from chipbench.operations import scan_pushdown

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _json(*parts):
    with open(os.path.join(ROOT, "chipbench", *parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def run(tmp_path_factory):
    config = _json("configs", "mor50m-dedup.json")
    run = types.SimpleNamespace(
        args=types.SimpleNamespace(seed=11), config=config,
        data=config["rehearsal_data"], setup={}, state={},
        tmp=str(tmp_path_factory.mktemp("pushdown")),
        traffic=_json("traffic", "mor_scan_pushdown.json"))
    # at the rehearsal's key space the cell's key bound keeps every key:
    # halve it as the cell's bound halves 25M keys
    run.traffic["predicate"]["and"][1][2] = run.data["key_space"] // 2
    scan_pushdown.prepare(run)
    return run


def test_the_cell_s_traffic_is_the_queue_s(run):
    traffic = _json("traffic", "mor_scan_pushdown.json")
    assert traffic["projection"] == ["id", "v3"]
    assert traffic["predicate"] == {"and": [["v3", "<", 10],
                                            ["id", "<", 12500000]]}


@pytest.mark.parametrize("route", ["PAIMON_FORCE_HOST_SORT",
                                   "PAIMON_FORCE_DEVICE_SORT", None])
def test_scan_equals_masked_reference(run, route, monkeypatch):
    if route:
        monkeypatch.setenv(route, "1")
    got = scan_pushdown.operation(run, run.state["base"])
    assert got.column_names == ["id", "v3"]
    assert 0 < got.num_rows == run.state["result_rows"] \
        < run.state["input_rows"] // 10
    reference.check_checksum(reference.table_checksum(got),
                             run.state["want_sum"], "scan")
    reference.check_equal(reference.columns_of(got), run.state["want"],
                          "scan")


def test_reference_is_the_masked_merge(run):
    runs = data.gen_runs(11, run.data["rows"], run.data["runs"],
                         run.data["key_space"],
                         run.config["data"]["key_seed"])
    merged = reference.merged(data.concat(runs), "deduplicate")
    keep = (merged["v3"] < 10) & (merged["id"] < run.data["key_space"] // 2)
    assert np.array_equal(run.state["want"]["id"], merged["id"][keep])
    assert np.array_equal(run.state["want"]["v3"], merged["v3"][keep])
