"""`trace_reduce.py` on a synthetic plane and on a trace recorded on the
chip (`data/agg_ingest.xplane.pb`, a traced `agg_ingest` run of PR 24)."""

import os

import pytest

from chipbench import trace_reduce as T

HERE = os.path.dirname(os.path.abspath(__file__))
SORT = ("%sort.3 = (u32[1024]{0:T(1024)S(1)}, s32[1024]{0:T(1024)}) "
        "sort(u32[1024]{0:T(1024)S(1)} %copy-done.1, s32[1024]{0:T(1024)} "
        "%iota.1), dimensions={0}, is_stable=true, to_apply=%region_0.1")
SEG = [("%custom-call.1 = u32[4096]{0:T(1024)S(1)} custom-call("
        "s64[4096]{0:T(1024)} %seg_ids.1), custom_call_target=\"X64SplitLow\""),
       ("%fusion = s32[2048]{0:T(1024)} fusion(u32[4096]{0:T(1024)S(1)} "
        "%custom-call.1, s32[4096]{0:T(1024)} %vals.1, s32[]{:T(128)} "
        "%constant.1), kind=kCustom, calls=%fused_computation.1")]


def _planes():
    """One chip.  Module A [1.0, 2.0] holds a sort [1.0, 1.5] and an op
    [1.4, 1.9] that overlaps it; module B [3.0, 3.5] holds two ops; one
    op [5.5, 6.5] straddles the window's end at 6.0."""
    modules = [("jit_fn(1)", 1.0, 2.0), ("jit__seg_max_jit(2)", 3.0, 3.5)]
    ops = [(SORT, 1.0, 1.5), ("%fn.1 = u32[8]{0} add(u32[8]{0} %a)", 1.4, 1.9),
           (SEG[0], 3.0, 3.1), (SEG[1], 3.1, 3.5),
           ("%late = u32[8]{0} add(u32[8]{0} %a)", 5.5, 6.5)]
    host = [("chipbench.window", 0.0, 6.0), ("chipbench.mix", 0.8, 2.5),
            ("chipbench.mix", 2.6, 5.0), ("other", 0.0, 9.0)]
    return [("/device:TPU:0", [(T.MODULES_LINE, modules), (T.OPS_LINE, ops)]),
            ("/host:CPU", [("python3", host)]),
            ("/host:metadata", [])]


def test_busy_is_the_union_and_not_the_sum():
    red = T.reduce_planes(_planes())
    assert red.window == (0.0, 6.0) and red.chips == 1
    # [1.0, 2.0] + [3.0, 3.5] + [5.5, 6.0]: the sum of all events is 4.4
    assert red.busy_s == pytest.approx(2.0)
    assert red.idle_share == pytest.approx(1 - 2.0 / 6.0)
    assert sum(red.op_seconds.values()) == pytest.approx(2.5)


def test_ops_are_named_by_module_and_summed():
    red = T.reduce_planes(_planes())
    assert red.op_seconds["jit_fn/sort.3"] == pytest.approx(0.5)
    assert red.op_seconds["jit__seg_max_jit/fusion"] == pytest.approx(0.4)
    assert red.module_seconds == {"jit_fn": pytest.approx(1.0),
                                  "jit__seg_max_jit": pytest.approx(0.5)}
    assert red.breakdown(top=1)["device_ops"] == [["late", 1.0]]


def test_idle_gaps_are_named_by_the_annotation_that_covers_them():
    red = T.reduce_planes(_planes())
    assert [(n, round(s, 6)) for n, s in red.idle_gaps] == [
        ("mix", 2.0), ("between_operations", 1.0), ("mix", 1.0)]
    # [3.5, 5.5] lies in the second annotation for 1.5 s of its 2 s; of
    # [0, 1] the first covers 0.2 s; of [2, 3] it covers half


def test_bytes_from_the_hlo_text():
    red = T.reduce_planes(_planes())
    sort = next(o for o in red.ops if o.name == "sort.3")
    assert T.result_bytes(SORT) == 2 * 1024 * 4
    assert T.sort_min_bytes(sort) == 2 * 2 * 1024 * 4
    seg = next(m for m in red.modules if "_seg_" in m.name)
    # inputs seg_ids (s64) and vals (s32) once, the result (s32[2048]) once
    assert T.module_min_bytes(seg.ops) == \
        4096 * 8 + 4096 * 4 + 2048 * 4
    assert T.result_bytes("sort.3") is None


def test_no_device_op_is_nothing_to_reduce():
    assert T.reduce_planes([("/host:CPU", [("t", [("x", 0.0, 1.0)])])]) is None


def test_without_a_window_annotation_the_device_events_bound_it():
    planes = [p for p in _planes() if p[0].startswith("/device")]
    red = T.reduce_planes(planes)
    assert red.window == (1.0, 6.5)
    assert red.busy_s == pytest.approx(1.0 + 0.5 + 1.0)


def test_unknown_device_kind_is_an_error():
    assert T.peak_bytes_per_s("TPU v5 lite") == 819e9
    with pytest.raises(KeyError):
        T.peak_bytes_per_s("TPU v9")


def test_recorded_trace_of_a_chip_run():
    """A traced `agg_ingest` run of PR 24 on a TPU v5 lite: 12 commits,
    each one 6-operand sort padded to 4Mi rows.  The expected values were
    computed apart from `trace_reduce`: busy by rasterising the device
    events onto a 100 ns timeline (0.2042698 s), the sums by adding the
    events' durations, the bytes by hand."""
    red = T.reduce_file(os.path.join(HERE, "data", "agg_ingest.xplane.pb"))
    assert red.chips == 1 and len(red.modules) == 12
    assert red.window_s == pytest.approx(32.084591315, rel=1e-9)
    assert red.busy_s == pytest.approx(0.204269554, rel=1e-6)
    assert red.busy_s == pytest.approx(red.module_seconds["jit_fn"])
    assert red.idle_share == pytest.approx(1 - 0.204269554 / 32.084591315)
    assert red.op_seconds["jit_fn/sort"] == pytest.approx(0.193871889)
    sorts = [o for o in red.ops if o.name.startswith("sort")]
    assert len(sorts) == 12
    # 6 operands of 4Mi x 4 B, read once and written once, 12 times
    assert sum(T.sort_min_bytes(o) for o in sorts) == \
        12 * 2 * 6 * 4194304 * 4
    top = red.breakdown()
    assert top["device_ops"][0][0] == "jit_fn/sort"
    assert len(top["idle_gaps"]) == 10
    assert top["idle_gaps"][0] == ["batch_ingest",
                                   pytest.approx(2.764499892)]
