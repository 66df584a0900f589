"""`span_reduce.py` and the `span_idle` reader: the leaf rule, the union
over threads, `uncovered` and `within` on a hand-written plane list; the
parse of `paimon.*` names from a trace recorded on the CPU
(`data/cpu_spans.xplane.pb`: two scans and one streamed full compaction
of a 3 x 3,000-row aggregation table under `jax.profiler.start_trace`
with `python_tracer_level = 0`, inside `chipbench.window` /
`chipbench.full_compact` annotations, tracing never enabled); and the
`[chipbench] spans` line of each cell's rehearsal trace."""

import json
import os
import subprocess
import sys
import types

import pytest

from chipbench import span_reduce as S
from chipbench import trace_reduce as T
from chipbench.readers import span_idle

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
CELLS = [w["name"] for w in MANIFEST["workloads"]]
LINK = ["paimon.merge.device", "paimon.agg.device"]


def _planes(spans=True, device=True):
    """One chip, busy [2, 3] and [7, 7.5]; window [0, 10]; operations
    [1, 5] and [6, 9].  So the device idles 5.5 s inside the operations
    — [1, 2], [3, 5], [6, 7], [7.5, 9] — and 3.0 s between them.

    Thread `main`: scan.split [1, 4] around decode [1, 1.5] and
    merge.device [1.8, 3.2]; wait [4, 5]; scan.assemble [6, 6.2].
    Thread `worker`: decode [1.2, 1.7] (overlaps main's); scan.split
    [3.5, 4.5] around decode [3.6, 4.4]; merge.device [7.2, 8]."""
    main = [("chipbench.window", 0.0, 10.0), ("chipbench.mix", 1.0, 5.0),
            ("chipbench.mix", 6.0, 9.0), ("other", 0.0, 10.0)]
    worker = []
    if spans:
        main += [("paimon.scan.split", 1.0, 4.0),
                 ("paimon.decode", 1.0, 1.5),
                 ("paimon.merge.device", 1.8, 3.2),
                 ("paimon.wait", 4.0, 5.0),
                 ("paimon.scan.assemble", 6.0, 6.2)]
        worker += [("paimon.decode", 1.2, 1.7),
                   ("paimon.scan.split", 3.5, 4.5),
                   ("paimon.decode", 3.6, 4.4),
                   ("paimon.merge.device", 7.2, 8.0)]
    planes = [("/host:CPU", [("main", main), ("worker", worker)]),
              ("/host:metadata", [])]
    if device:
        ops = [("%sort.1 = u32[8]{0} sort(u32[8]{0} %a)", 2.0, 3.0),
               ("%fusion.2 = u32[8]{0} fusion(u32[8]{0} %a)", 7.0, 7.5)]
        planes.insert(0, ("/device:TPU:0",
                          [(T.MODULES_LINE, [("jit_fn(1)", 2.0, 3.0)]),
                           (T.OPS_LINE, ops)]))
    return planes


def test_idle_is_the_devices_gaps_inside_the_operations():
    red = S.reduce_planes(_planes())
    assert red.has_device and red.window == (0.0, 10.0)
    assert red.idle == [(1.0, 2.0), (3.0, 5.0), (6.0, 7.0), (7.5, 9.0)]
    assert red.idle_s == pytest.approx(5.5)
    assert red.between_s == pytest.approx(3.0)
    # the same busy time as trace_reduce's
    assert T.reduce_planes(_planes()).busy_s == pytest.approx(
        10.0 - red.idle_s - red.between_s)


def test_only_leaves_attribute_and_an_envelope_covers_nothing():
    red = S.reduce_planes(_planes())
    by_name = {}
    for s in red.spans:
        by_name.setdefault(s.name, []).append(s)
    assert all(not s.leaf for s in by_name["paimon.scan.split"])
    assert all(s.leaf for n, ss in by_name.items()
               if n != "paimon.scan.split" for s in ss)
    assert "paimon.scan.split" not in red.idle_by_leaf
    # self time: main's split 3.0 - 0.5 - 1.4, the worker's 1.0 - 0.8
    assert red.self_s["paimon.scan.split"] == pytest.approx(1.3)
    assert red.self_s["paimon.decode"] == pytest.approx(0.5 + 0.5 + 0.8)


def test_a_name_is_the_union_over_threads():
    red = S.reduce_planes(_planes())
    # decode: [1, 1.7] (two threads, overlapping) and [3.6, 4.4]
    assert red.idle_by_leaf["paimon.decode"] == pytest.approx(0.7 + 0.8)
    assert red.idle_by_leaf["paimon.merge.device"] == pytest.approx(
        0.2 + 0.2 + 0.5)
    assert red.idle_by_leaf["paimon.scan.assemble"] == pytest.approx(0.2)
    # the names sum to more than the idle time they cover together
    assert red.idle_by_leaf["paimon.wait"] == pytest.approx(1.0)


def test_uncovered_leaves_out_waits_and_envelopes():
    red = S.reduce_planes(_planes())
    # cover: [1, 1.7] [1.8, 2] [3, 3.2] [3.6, 4.4] [6, 6.2] [7.5, 8];
    # the wait over [4.4, 5] and the splits' own time cover nothing
    assert red.uncovered_s == pytest.approx(5.5 - 2.6)
    assert red.uncovered_share() == pytest.approx(100 * 2.9 / 5.5)


def test_within_is_the_idle_share_of_the_round_trips():
    red = S.reduce_planes(_planes())
    # open [1.8, 3.2] and [7.2, 8]: 2.2 s, of which the device idles
    # [1.8, 2] [3, 3.2] [7.5, 8]
    assert red.idle_share_within(LINK) == pytest.approx(100 * 0.9 / 2.2)
    assert red.idle_share_within(["paimon.agg.device"]) == 0.0


def test_a_trace_without_program_spans_is_all_uncovered():
    red = S.reduce_planes(_planes(spans=False))
    assert red.spans == [] and red.idle_by_leaf == {}
    assert red.uncovered_share() == pytest.approx(100.0)
    assert red.idle_share_within(LINK) == 0.0


def test_without_a_device_plane_the_operations_are_all_idle():
    red = S.reduce_planes(_planes(device=False))
    assert not red.has_device
    assert red.idle_s == pytest.approx(7.0)
    assert red.between_s == pytest.approx(3.0)


def test_intersect_and_nest():
    assert S.intersect([(0, 2), (3, 5)], [(1, 4), (4.5, 9)]) == \
        [(1, 2), (3, 4), (4.5, 5)]
    spans = S.nest([("paimon.b", 1.0, 2.0), ("paimon.a", 0.0, 5.0),
                    ("paimon.c", 1.2, 1.8), ("paimon.d", 5.0, 6.0)])
    assert [(s.name, s.leaf) for s in spans] == \
        [("paimon.a", False), ("paimon.b", False), ("paimon.c", True),
         ("paimon.d", True)]
    assert spans[0].self_s == pytest.approx(4.0)


def test_program_spans_parse_from_a_recorded_cpu_trace():
    red = S.reduce_file(os.path.join(HERE, "data", "cpu_spans.xplane.pb"))
    assert not red.has_device           # recorded on the CPU
    names = {s.name for s in red.spans}
    for name in ("paimon.scan.to_arrow", "paimon.scan.split",
                 "paimon.decode", "paimon.merge.prep", "paimon.merge.host",
                 "paimon.agg.reduce", "paimon.agg.device",
                 "paimon.compact.task", "paimon.compact.window",
                 "paimon.merge.cut", "paimon.wait", "paimon.encode",
                 "paimon.commit"):
        assert name in names, name
    leaves = {s.name for s in red.spans if s.leaf}
    assert "paimon.compact.task" not in leaves
    assert "paimon.scan.split" not in leaves
    assert {"paimon.decode", "paimon.agg.device",
            "paimon.merge.cut"} <= leaves
    # three operations, back to back, fill the window
    assert red.idle_s == pytest.approx(red.window[1] - red.window[0],
                                       rel=1e-3)
    assert 0.0 <= red.uncovered_share() < 50.0
    line = red.line()
    assert line.startswith("[chipbench] spans {")
    body = json.loads(line[len("[chipbench] spans "):])
    assert body["device"] is False and "agg.device" in body["idle_s_by_leaf"]
    assert "compact.task" in body["self_ms"]


def _run(trace, cell="agg_ingest", seed=7):
    return types.SimpleNamespace(
        trace=trace, cell={"name": cell},
        args=types.SimpleNamespace(seed=seed))


def test_the_reader_reduces_once_and_prints_the_spans_line(monkeypatch,
                                                           capsys):
    calls = []

    def reduce_file(path):
        calls.append(path)
        return S.reduce_planes(_planes())

    monkeypatch.setattr(span_idle, "_reductions", {})
    monkeypatch.setattr(span_idle.trace_reduce, "find_xplane",
                        lambda d: os.path.join(d, "t.xplane.pb"))
    monkeypatch.setattr(span_idle.span_reduce, "reduce_file", reduce_file)
    run = _run(trace=object())
    assert span_idle.read(run, {"uncovered": True}) == \
        pytest.approx(100 * 2.9 / 5.5)
    assert span_idle.read(run, {"within": LINK}) == \
        pytest.approx(100 * 0.9 / 2.2)
    assert len(calls) == 1 and calls[0].endswith(os.path.join(
        "chiprun_out", "chipbench", "traces", "agg_ingest.seed7",
        "t.xplane.pb"))
    out = capsys.readouterr().out.strip().splitlines()
    assert len(out) == 1 and out[0].startswith("[chipbench] spans {")
    # another run, another trace
    assert span_idle.read(_run(object(), seed=8), {"uncovered": True}) \
        is not None
    assert len(calls) == 2


def test_the_reader_has_nothing_to_read_without_a_device_trace(
        monkeypatch):
    monkeypatch.setattr(span_idle, "_reductions", {})
    assert span_idle.read(_run(trace=None), {"uncovered": True}) is None
    monkeypatch.setattr(span_idle.trace_reduce, "find_xplane",
                        lambda d: "t.xplane.pb")
    monkeypatch.setattr(span_idle.span_reduce, "reduce_file",
                        lambda p: S.reduce_planes(_planes(device=False)))
    assert span_idle.read(_run(object()), {"within": LINK}) is None
    # the parent of the PR that brought the spans: a device, no span
    monkeypatch.setattr(span_idle, "_reductions", {})
    monkeypatch.setattr(span_idle.span_reduce, "reduce_file",
                        lambda p: S.reduce_planes(_planes(spans=False)))
    assert span_idle.read(_run(object()), {"within": LINK}) == 0.0
    assert span_idle.read(_run(object()), {"uncovered": True}) == \
        pytest.approx(100.0)


@pytest.mark.parametrize("cell", CELLS)
def test_a_rehearsal_trace_gives_the_spans_line(cell):
    """`run.py` calls no reader in a rehearsal, so the line comes from
    the reduction's own command, on the trace the rehearsal left."""
    seed = "3000000023"
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    done = subprocess.run(
        [sys.executable, *MANIFEST["command"][1:], "--workload", cell,
         "--seed", seed, "--seconds", "1", "--trace", "1", "--rehearsal"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    traces = os.path.join(ROOT, "chiprun_out", "chipbench", "traces",
                          f"{cell}.seed{seed}")
    done = subprocess.run(
        [sys.executable, "-m", "chipbench.span_reduce", traces],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert done.returncode == 0, done.stderr[-2000:]
    line = done.stdout.strip().splitlines()[-1]
    assert line.startswith("[chipbench] spans ")
    body = json.loads(line[len("[chipbench] spans "):])
    assert body["device"] is False and body["spans"] > 0
    assert body["idle_s_by_leaf"] and body["self_ms"]
    # every operation of the cell ran under the program's root span
    roots = {"agg_compact": "compact.task", "dedup_scan": "scan.to_arrow",
             "agg_ingest": "write.prepare"}
    assert roots[cell] in body["self_ms"]
    assert body["uncovered_s"] < body["idle_s"]
