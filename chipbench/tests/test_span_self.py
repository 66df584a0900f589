"""The `span_self` reader (`host_unspanned_ms_per_mrow.*`) on a
hand-written plane list and on the trace recorded on the CPU
(`data/cpu_spans.xplane.pb`, see `test_span_reduce.py`); and every metric
file of PR 36 against the reader it names."""

import importlib
import inspect
import json
import os
import types

import pytest

from chipbench import span_reduce as S
from chipbench import trace_reduce as T
from chipbench.readers import span_idle, span_self

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
NEW_METRICS = [
    "merge_winners_ms_per_mrow.scan", "merge_winners_ms_per_mrow.compact",
    "file_stats_ms_per_mrow.compact", "file_stats_ms_per_mrow.ingest",
    "write_build_ms_per_mrow", "agg_mask_ms_per_mrow",
    "merge_host_ms_per_mrow.compact", "merge_device_inflight.scan",
    "merge_device_inflight.compact", "merge_prep_planar_share.scan",
    "merge_prep_planar_share.compact", "write_route_nocopy_share.ingest",
    "host_unspanned_ms_per_mrow.scan", "host_unspanned_ms_per_mrow.compact",
    "host_unspanned_ms_per_mrow.ingest"]


def _planes():
    """Window [0, 20]; operations [1, 5] and [6, 9].

    Thread `main`: scan.to_arrow [1, 5] around scan.plan [1, 1.2] and a
    wait [1.5, 4.8] (self 0.5); between the operations a scan.to_arrow
    [5.2, 5.8] around a scan.plan [5.3, 5.4] (the harness's checksum);
    scan.to_arrow [6, 9] around a wait [6.1, 8.9] (self 0.2).
    Thread `worker`: scan.split [1.5, 4.5] around decode [1.5, 2.5] and
    scan.merge [2.6, 4.4], which holds merge.device [2.7, 3.7] and
    merge.winners [3.8, 4.3] (split self 0.2, merge self 0.3).
    Thread `waiter`: one wait [1, 9], no envelope."""
    main = [("chipbench.window", 0.0, 20.0), ("chipbench.mix", 1.0, 5.0),
            ("chipbench.mix", 6.0, 9.0),
            ("paimon.scan.to_arrow", 1.0, 5.0),
            ("paimon.scan.plan", 1.0, 1.2), ("paimon.wait", 1.5, 4.8),
            ("paimon.scan.to_arrow", 5.2, 5.8),
            ("paimon.scan.plan", 5.3, 5.4),
            ("paimon.scan.to_arrow", 6.0, 9.0), ("paimon.wait", 6.1, 8.9)]
    worker = [("paimon.scan.split", 1.5, 4.5), ("paimon.decode", 1.5, 2.5),
              ("paimon.scan.merge", 2.6, 4.4),
              ("paimon.merge.device", 2.7, 3.7),
              ("paimon.merge.winners", 3.8, 4.3)]
    waiter = [("paimon.wait", 1.0, 9.0)]
    return [("/device:TPU:0",
             [(T.MODULES_LINE, [("jit_fn(1)", 2.8, 3.6)]),
              (T.OPS_LINE, [("%sort.1 = u32[8]{0} sort(u32[8]{0} %a)",
                             2.8, 3.6)])]),
            ("/host:CPU", [("main", main), ("worker", worker),
                           ("waiter", waiter)]),
            ("/host:metadata", [])]


def test_operations_are_the_harness_annotations_without_the_window():
    assert span_self.operations(_planes()) == [(1.0, 5.0), (6.0, 9.0)]


def test_an_envelope_gives_its_duration_less_its_children():
    planes = _planes()
    own, leaves = span_self.self_seconds(
        S.reduce_planes(planes).spans, span_self.operations(planes))
    assert own == {"paimon.scan.to_arrow": pytest.approx(0.5 + 0.2),
                   "paimon.scan.split": pytest.approx(0.2),
                   "paimon.scan.merge": pytest.approx(0.3)}
    # plan, decode, the round trip, the winners: no wait among them
    assert leaves == pytest.approx(0.2 + 1.0 + 1.0 + 0.5)


def test_a_span_between_two_operations_counts_nothing():
    planes = _planes()
    spans = S.reduce_planes(planes).spans
    between = [s for s in spans if 5.0 < s.start < 6.0]
    assert {s.name for s in between} == {"paimon.scan.to_arrow",
                                         "paimon.scan.plan"}
    own, leaves = span_self.self_seconds(spans,
                                         span_self.operations(planes))
    with_it, more = span_self.self_seconds(spans, [(0.0, 20.0)])
    assert with_it["paimon.scan.to_arrow"] - own["paimon.scan.to_arrow"] \
        == pytest.approx(0.5)
    assert more - leaves == pytest.approx(0.1)


def test_a_thread_that_only_waits_counts_nothing():
    planes = _planes()
    ops = span_self.operations(planes)
    whole = span_self.self_seconds(S.reduce_planes(planes).spans, ops)
    host = planes[1][1]
    planes[1] = ("/host:CPU", [ln for ln in host if ln[0] != "waiter"])
    assert span_self.self_seconds(S.reduce_planes(planes).spans, ops) \
        == whole


def _run(rows=2_000_000, trace=object(), cell="dedup_scan", seed=7):
    return types.SimpleNamespace(
        trace=trace, rows=rows, cell={"name": cell},
        args=types.SimpleNamespace(seed=seed))


def test_the_reader_reduces_once_and_divides_by_the_rows(monkeypatch,
                                                         capsys):
    reduced, parsed = [], []

    def reduce_file(path):
        reduced.append(path)
        return S.reduce_planes(_planes())

    def read_planes(path):
        parsed.append(path)
        return _planes()

    monkeypatch.setattr(span_idle, "_reductions", {})
    monkeypatch.setattr(span_self, "_operations", {})
    monkeypatch.setattr(T, "find_xplane",
                        lambda d: os.path.join(d, "t.xplane.pb"))
    monkeypatch.setattr(S, "reduce_file", reduce_file)
    monkeypatch.setattr(T, "read_planes", read_planes)
    run = _run()
    # 1.2 s of envelopes' own time over 2 Mrows
    assert span_self.read(run, {}) == pytest.approx(600.0)
    # the reduction is `span_idle`'s, whichever reader came first
    assert span_idle.read(run, {"uncovered": True}) is not None
    assert span_self.read(run, {}) == pytest.approx(600.0)
    assert len(reduced) == 1 and len(parsed) == 1
    assert reduced[0].endswith(os.path.join(
        "chiprun_out", "chipbench", "traces", "dedup_scan.seed7",
        "t.xplane.pb"))
    lines = capsys.readouterr().out.strip().splitlines()
    assert lines[0].startswith("[chipbench] spans {")
    body = json.loads(lines[1][len("[chipbench] span_self "):])
    assert body["operations"] == 2
    assert body["envelopes_self_ms"] == pytest.approx(1200.0)
    assert body["leaves_ms"] == pytest.approx(2700.0)
    assert list(body["self_ms"]) == ["scan.to_arrow", "scan.merge",
                                     "scan.split"]


def test_nothing_to_read_without_a_trace_or_rows(monkeypatch):
    monkeypatch.setattr(span_idle, "_reductions", {})
    monkeypatch.setattr(span_self, "_operations", {})
    monkeypatch.setattr(T, "find_xplane", lambda d: None)
    assert span_self.read(_run(), {}) is None
    monkeypatch.setattr(span_idle, "_reductions", {})
    monkeypatch.setattr(T, "find_xplane", lambda d: "t.xplane.pb")
    monkeypatch.setattr(S, "reduce_file",
                        lambda p: S.reduce_planes(_planes()))
    monkeypatch.setattr(T, "read_planes", lambda p: _planes())
    assert span_self.read(_run(rows=0), {}) is None
    assert span_self.read(_run(), {}) is not None


def test_the_recorded_cpu_trace_names_its_envelopes():
    path = os.path.join(HERE, "data", "cpu_spans.xplane.pb")
    planes = T.read_planes(path)
    ops = span_self.operations(planes)
    red = S.reduce_planes(planes)
    assert len(ops) == 3                # two scans, one full compaction
    own, leaves = span_self.self_seconds(red.spans, ops)
    assert {"paimon.scan.to_arrow", "paimon.scan.split",
            "paimon.compact.task", "paimon.compact.window",
            "paimon.agg.reduce"} <= set(own)
    assert not {"paimon.decode", "paimon.agg.device", "paimon.wait",
                "paimon.merge.cut"} & set(own)
    assert all(v >= 0.0 for v in own.values()) and leaves > 0.0
    # every envelope of the trace began inside an operation here, so the
    # sums are the reduction's own self times
    for name, seconds in own.items():
        assert seconds == pytest.approx(red.self_s[name])


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_a_new_metric_names_a_reader_that_takes_its_params(metric):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = next(m for m in manifest["per_layer"] if m["name"] == metric)
    cells = {w["name"] for w in manifest["workloads"]}
    assert entry["workloads"] and set(entry["workloads"]) <= cells
    moved = next(m for m in manifest["end_to_end"]
                 if m["name"] == entry["moves"])
    assert set(entry["workloads"]) <= set(moved["workloads"])
    with open(os.path.join(ROOT, "chipbench", "metrics",
                           metric + ".json")) as f:
        spec = json.load(f)
    assert set(spec) == {"reader", "params"}
    reader = importlib.import_module("chipbench.readers." + spec["reader"])
    assert list(inspect.signature(reader.read).parameters) == \
        ["run", "params"]
    params = spec["params"]
    accepted = {"registry_ms_per_mrow": {"group", "metric"},
                "counter_ratio": {"numerator", "denominator", "scale"},
                "span_self": set()}[spec["reader"]]
    assert set(params) == accepted
    # the reader takes them: a run with nothing to read gives None
    registry = {}
    run = types.SimpleNamespace(
        trace=None, rows=0, ops=[], cell={"name": "no_such_cell"},
        args=types.SimpleNamespace(seed=0),
        counters=types.SimpleNamespace(registry=registry))
    assert reader.read(run, params) is None
    if spec["reader"] == "counter_ratio":
        registry[tuple(params["denominator"])] = 4
        registry[tuple(params["numerator"])] = 3
        assert reader.read(run, params) == params["scale"] * 0.75
    elif spec["reader"] == "registry_ms_per_mrow":
        registry[(params["group"], params["metric"])] = 50.0
        run.rows = 2_000_000
        assert reader.read(run, params) == 25.0
