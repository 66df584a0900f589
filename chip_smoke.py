#!/usr/bin/env python3
"""Chip smoke: the system's main path, once, on the attached TPU.

    python chip_smoke.py                 # on a machine with a chip
    python chip_smoke.py --platform cpu --rows 200000    # rehearsal

Creates primary-key tables, writes overlapping sorted runs, commits,
runs full compaction, merge-on-read scans and point lookups through
`FileStoreTable`, `compact`, `lookup.LocalTableQuery` and
`parallel.compact_table_mesh`, and compares every row that comes out
with a plain numpy reference written here, independent of `paimon_tpu`.

* Phase A: the default route at a real size — no pin, no non-default
  option — on the bench's own table shape (bench.py `build_table`).
* Phase B: every device program, pinned, at a smaller size: the two
  device-sort return formats, the mesh window kernel and the device
  decode plane, on an aggregation and a deduplicate table.

One process, nothing spawned, no network, data made from `--seed`.  The
run FAILS (non-zero exit, no result line) when JAX finds no TPU unless
`--platform cpu` marks it a rehearsal, when the native library is
missing, when any row differs from the reference, or when any device
step was quietly done another way.  The last line of standard output is
the result, one JSON object with exactly two keys:

    {"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}

The line before it, `[chip_smoke] report {...}`, is the full report
(header, both phases, path and compile counters per step); the same
report is written to `chiprun_out/chip_smoke.json`.  Its timings are
smoke timings for the next issue to plan from, not metrics.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import shutil
import sys
import tempfile
import time
import traceback
from concurrent.futures import ThreadPoolExecutor

DEFAULT_ROWS = 32_000_000       # 4 x tpu.merge.stream-threshold-rows
RUNS_A = 10                     # overlapping sorted runs, keys [0, rows/2)
RUNS_B = 5
LOOKUPS = 1000
OUT_DIR = "chiprun_out"


class SmokeFailure(Exception):
    """A check of the smoke did not hold."""


def _parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=DEFAULT_ROWS,
                    help="Phase A rows (Phase B takes a quarter)")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--platform", choices=["cpu"], default=None,
                    help="explicit CPU rehearsal; never inferred")
    return ap.parse_args(argv)


# ---------------------------------------------------------------------------
# data and the plain reference (numpy only)
# ---------------------------------------------------------------------------


def _gen_runs(np, rng, rows: int, runs: int):
    """bench.py `build_table`'s columns: id BIGINT in [0, rows/2),
    v1 BIGINT, v2 DOUBLE, v3 INT."""
    per_run = rows // runs
    out = []
    for _ in range(runs):
        out.append({
            "id": rng.integers(0, max(rows // 2, 1), per_run),
            "v1": rng.integers(0, 1 << 40, per_run),
            "v2": rng.random(per_run),
            "v3": rng.integers(0, 100, per_run).astype(np.int32),
        })
    return out


def _concat(np, run_list):
    return {k: np.concatenate([r[k] for r in run_list])
            for k in run_list[0]}


def _reference(np, cols, engine: str):
    """Stable sort by key (arrival order breaks ties, so later commits
    come later), then last-by-sequence (deduplicate) or
    sum(v1) / max(v2) / max(v3) (aggregation)."""
    order = np.argsort(cols["id"], kind="stable")
    sid = cols["id"][order]
    start = np.concatenate([[True], sid[1:] != sid[:-1]])
    if engine == "deduplicate":
        last = np.concatenate([sid[1:] != sid[:-1], [True]])
        win = order[last]
        return {k: v[win] for k, v in cols.items()}
    starts = np.flatnonzero(start)
    return {"id": sid[starts],
            "v1": np.add.reduceat(cols["v1"][order], starts),
            "v2": np.maximum.reduceat(cols["v2"][order], starts),
            "v3": np.maximum.reduceat(cols["v3"][order], starts)}


def _bits(np, a):
    """Compare floats by their bits: equal means identical."""
    if a.dtype == np.float64:
        return a.view(np.uint64)
    return a


def _check_table(np, got, want: dict, what: str):
    """Every column of `got` (a pyarrow table) equals the reference
    exactly after sorting by key."""
    if got.num_rows != len(want["id"]):
        raise SmokeFailure(f"{what}: {got.num_rows} rows, reference has "
                           f"{len(want['id'])}")
    ids = got.column("id").to_numpy()
    order = np.argsort(ids, kind="stable")
    for name, ref in want.items():
        col = got.column(name)
        if col.null_count:
            raise SmokeFailure(f"{what}: {col.null_count} nulls in {name}")
        have = col.to_numpy()[order]
        if have.dtype != ref.dtype:
            raise SmokeFailure(f"{what}: {name} is {have.dtype}, "
                               f"reference {ref.dtype}")
        bad = np.flatnonzero(_bits(np, have) != _bits(np, ref))
        if len(bad):
            i = int(bad[0])
            raise SmokeFailure(
                f"{what}: {len(bad)} rows differ in {name}; first at key "
                f"{int(ids[order][i])}: got {have[i]!r}, reference "
                f"{ref[i]!r}")


# ---------------------------------------------------------------------------
# counters
# ---------------------------------------------------------------------------


class _CompileMeter:
    """Backend compiles (count, seconds) and persistent-cache hits and
    misses, from jax.monitoring."""

    def __init__(self, jax):
        self.compiles = 0
        self.seconds = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.seconds += duration

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def snapshot(self):
        return (self.compiles, self.seconds, self.cache_hits,
                self.cache_misses)

    def since(self, snap):
        now = self.snapshot()
        return {"backend_compiles": now[0] - snap[0],
                "backend_compile_s": round(now[1] - snap[1], 2),
                "persistent_cache_hits": now[2] - snap[2],
                "persistent_cache_misses": now[3] - snap[3]}


class _Recorder:
    """Per step: wall seconds, merges per path, the first routing
    decision, the compiles it caused."""

    def __init__(self, merge_mod, meter):
        self.M = merge_mod
        self.meter = meter
        self.steps = []

    @contextlib.contextmanager
    def step(self, name: str):
        M = self.M
        paths0 = dict(M.PATH_COUNTS)
        del M.ROUTE_LOG[:]
        snap = self.meter.snapshot()
        t0 = time.perf_counter()
        yield
        rec = {"step": name,
               "smoke_wall_s": round(time.perf_counter() - t0, 2),
               "merge_paths": {k: M.PATH_COUNTS[k] - paths0[k]
                               for k in paths0},
               "first_route": M.ROUTE_LOG[0] if M.ROUTE_LOG else None,
               "routes": sorted({r["route"] for r in M.ROUTE_LOG}),
               "link_bytes_per_s": M._LINK_BW,
               **self.meter.since(snap)}
        self.steps.append(rec)
        print(f"[chip_smoke] {json.dumps(rec)}", flush=True)

    def last(self):
        return self.steps[-1]


def _counter(group: str, name: str) -> int:
    from paimon_tpu.metrics import global_registry
    return global_registry().group(group).counter(name).count


@contextlib.contextmanager
def _pins(**env):
    """Environment pins of Phase B: set in-process, for the block only."""
    old = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    try:
        yield
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


# ---------------------------------------------------------------------------
# tables
# ---------------------------------------------------------------------------

_BENCH_OPTIONS = {"write-only": "true",
                  "parquet.enable.dictionary": "false"}
_AGG_OPTIONS = {"merge-engine": "aggregation",
                "fields.v1.aggregate-function": "sum",
                "fields.v2.aggregate-function": "max",
                "fields.v3.aggregate-function": "max"}


def _create_table(path: str, buckets: int, engine: str):
    from paimon_tpu.schema import Schema
    from paimon_tpu.table import FileStoreTable
    from paimon_tpu.types import BigIntType, DoubleType, IntType

    options = {"bucket": str(buckets), **_BENCH_OPTIONS}
    if engine == "aggregation":
        options.update(_AGG_OPTIONS)
    schema = (Schema.builder()
              .column("id", BigIntType(False))
              .column("v1", BigIntType())
              .column("v2", DoubleType())
              .column("v3", IntType())
              .primary_key("id")
              .options(options)
              .build())
    return FileStoreTable.create(path, schema)


def _write_runs(table, run_list):
    import pyarrow as pa
    for run in run_list:
        data = pa.table({"id": pa.array(run["id"], pa.int64()),
                         "v1": pa.array(run["v1"], pa.int64()),
                         "v2": pa.array(run["v2"], pa.float64()),
                         "v3": pa.array(run["v3"], pa.int32())})
        wb = table.new_batch_write_builder()
        with wb.new_write() as w:
            w.write_arrow(data)
            wb.new_commit().commit(w.prepare_commit())


# ---------------------------------------------------------------------------
# phase A: the default route at a real size
# ---------------------------------------------------------------------------


def _phase_a(np, rec, tmp: str, rows: int, seed: int):
    from paimon_tpu import predicate as P
    from paimon_tpu.lookup import LocalTableQuery
    from paimon_tpu.metrics import (LOOKUP_NATIVE_FALLBACKS,
                                    LOOKUP_NATIVE_PROBES)
    from paimon_tpu.table import FileStoreTable

    rng = np.random.default_rng(seed)
    run_list = _gen_runs(np, rng, rows, RUNS_A)
    cols = _concat(np, run_list)
    want = _reference(np, cols, "deduplicate")
    path = os.path.join(tmp, "a")
    table = _create_table(path, 1, "deduplicate")

    with rec.step("A.write x%d" % RUNS_A):
        _write_runs(table, run_list)
    del run_list
    twin = os.path.join(tmp, "a_twin")       # same runs, for pass two
    shutil.copytree(path, twin)

    with rec.step("A.compact"):
        if table.compact(full=True) is None:
            raise SmokeFailure("A.compact committed nothing")
    compact_rec = rec.last()

    table = FileStoreTable.load(path)
    with rec.step("A.scan"):
        got = table.to_arrow()
    _check_table(np, got, want, "A.scan")
    del got

    key_cap = max(rows // 8, 1)
    with rec.step("A.scan projected+predicated"):
        got = table.to_arrow(
            projection=["id", "v3"],
            predicate=P.and_(P.greater_or_equal("v3", 50),
                             P.less_than("id", key_cap)))
    keep = (want["v3"] >= 50) & (want["id"] < key_cap)
    _check_table(np, got, {"id": want["id"][keep], "v3": want["v3"][keep]},
                 "A.scan projected+predicated")

    present = rng.choice(len(want["id"]), LOOKUPS * 9 // 10, replace=False)
    keys = [int(want["id"][i]) for i in present]
    keys += list(range(rows // 2, rows // 2 + LOOKUPS - len(keys)))
    by_id = {int(want["id"][i]): {n: want[n][i].item() for n in want}
             for i in present}
    fallbacks0 = _counter("lookup", LOOKUP_NATIVE_FALLBACKS)
    probes0 = _counter("lookup", LOOKUP_NATIVE_PROBES)
    query = LocalTableQuery(table, cache_dir=os.path.join(tmp, "a_sst"))
    try:
        with rec.step("A.lookup x%d" % len(keys)):
            rows_out = query.lookup([{"id": k} for k in keys])
    finally:
        query.close()
    for k, row in zip(keys, rows_out):
        if row != by_id.get(k):
            raise SmokeFailure(f"A.lookup: key {k} answered {row!r}, "
                               f"reference {by_id.get(k)!r}")
    native_fallbacks = _counter("lookup", LOOKUP_NATIVE_FALLBACKS) \
        - fallbacks0
    native_probes = _counter("lookup", LOOKUP_NATIVE_PROBES) - probes0
    if native_fallbacks or not native_probes:
        raise SmokeFailure(f"A.lookup: native_fallbacks={native_fallbacks} "
                           f"native_probes={native_probes}")

    with rec.step("A.compact second pass"):
        if FileStoreTable.load(twin).compact(full=True) is None:
            raise SmokeFailure("A.compact second pass committed nothing")
    second = rec.last()
    if second["backend_compiles"]:
        raise SmokeFailure(
            f"A.compact second pass compiled "
            f"{second['backend_compiles']} programs; the first pass "
            f"should have left none to compile")
    if second["merge_paths"] != compact_rec["merge_paths"]:
        raise SmokeFailure(
            f"A.compact second pass took other merge paths "
            f"{second['merge_paths']} than the first "
            f"{compact_rec['merge_paths']}")
    return {"rows": rows, "runs": RUNS_A, "keys": len(want["id"]),
            "lookups": len(keys), "native_probes": native_probes,
            "native_fallbacks": native_fallbacks}


# ---------------------------------------------------------------------------
# phase B: every device program, pinned
# ---------------------------------------------------------------------------

_DEVICE = {"PAIMON_FORCE_DEVICE_SORT": "1"}
_DECODE = {"read.device-decode": "true"}
# (name, engine, dynamic table options, mesh?).  The variants run side
# by side under one set of pins: on the chip a variadic lax.sort takes
# minutes to compile and each return format is its own program, so six
# in a row would not fit the time limit while six at once cost little
# more than one.
_VARIANTS = [("dedup.packed", "deduplicate", {}, False),
             ("agg.full-perm", "aggregation", {}, False),
             ("dedup.mesh", "deduplicate", {}, True),
             ("agg.mesh", "aggregation", {}, True),
             ("dedup.device-decode", "deduplicate", _DECODE, False),
             ("agg.device-decode", "aggregation", _DECODE, False)]


def _run_variant(np, jax, name, path, want, options, mesh):
    """merge-on-read scan, full compaction, scan again — each scan
    checked against the reference."""
    from paimon_tpu.parallel import compact_table_mesh
    from paimon_tpu.table import FileStoreTable

    t0 = time.perf_counter()
    out = {"variant": name}
    table = FileStoreTable.load(path)
    if options:
        table = table.copy(options)
    _check_table(np, table.to_arrow(), want, f"B.{name} merge-on-read scan")
    if mesh:
        stats = compact_table_mesh(table)
        n_dev = jax.local_device_count()
        out["mesh"] = {"lanes": stats.lanes, "lane_rows": stats.lane_rows,
                       "windows": stats.windows, "retries": stats.retries,
                       "fallbacks": stats.fallbacks}
        if stats.snapshot_id is None or stats.retries or stats.fallbacks \
                or not stats.windows or stats.lanes != n_dev \
                or len(stats.lane_rows) != n_dev \
                or not all(r > 0 for r in stats.lane_rows):
            raise SmokeFailure(f"B.{name}: mesh compaction {out['mesh']} "
                               f"on {n_dev} devices")
    elif table.compact(full=True) is None:
        raise SmokeFailure(f"B.{name}: compaction committed nothing")
    table = FileStoreTable.load(path)
    if options:
        table = table.copy(options)
    _check_table(np, table.to_arrow(), want, f"B.{name} compacted scan")
    out["smoke_wall_s"] = round(time.perf_counter() - t0, 2)
    return out


def _phase_b(np, jax, rec, tmp: str, rows: int, seed: int):
    from paimon_tpu.metrics import (COMPACTION_BUCKET_FALLBACKS,
                                    COMPACTION_BUCKET_RETRIES,
                                    SCAN_DEVICE_DECODE_FALLBACKS,
                                    SCAN_DEVICE_DECODE_FILES)
    from paimon_tpu.ops import merge as M
    from paimon_tpu.parallel import mesh_engine

    n_dev = jax.local_device_count()
    buckets = max(8, 2 * n_dev)
    rng = np.random.default_rng(seed + 1)
    run_list = _gen_runs(np, rng, rows, RUNS_B)
    cols = _concat(np, run_list)
    want = {e: _reference(np, cols, e)
            for e in ("deduplicate", "aggregation")}
    base = {}
    # the builds are not under test here (phase A wrote through the
    # default route): keep their flush sorts off the device so that they
    # add no program of their own shape
    with _pins(PAIMON_FORCE_HOST_SORT="1"), rec.step("B.build"):
        for engine in want:
            base[engine] = os.path.join(tmp, "b_" + engine)
            _write_runs(_create_table(base[engine], buckets, engine),
                        run_list)
    del run_list, cols

    paths = {}
    for name, engine, _, _ in _VARIANTS:
        paths[name] = os.path.join(tmp, "b_" + name)
        shutil.copytree(base[engine], paths[name])
    decode0 = (_counter("scan", SCAN_DEVICE_DECODE_FILES),
               _counter("scan", SCAN_DEVICE_DECODE_FALLBACKS))
    ladder0 = (_counter("compaction", COMPACTION_BUCKET_RETRIES),
               _counter("compaction", COMPACTION_BUCKET_FALLBACKS))
    label = "B[%s]" % ",".join(n for n, _, _, _ in _VARIANTS)
    with _pins(**_DEVICE), rec.step(label), \
            ThreadPoolExecutor(max_workers=len(_VARIANTS)) as pool:
        futures = [pool.submit(_run_variant, np, jax, name,
                               paths[name], want[engine], options, mesh)
                   for name, engine, options, mesh in _VARIANTS]
        variants = [f.result() for f in futures]
    step = rec.last()
    step["pins"] = _DEVICE
    if step["merge_paths"]["device"] <= 0 \
            or step["merge_paths"]["host"] \
            or step["merge_paths"]["ovc"]:
        raise SmokeFailure(f"{label}: pinned to the device, merges "
                           f"went {step['merge_paths']}")
    if step["routes"] != ["device"]:
        raise SmokeFailure(f"{label}: routes {step['routes']}, "
                           f"expected ['device']")
    files = _counter("scan", SCAN_DEVICE_DECODE_FILES) - decode0[0]
    fallbacks = _counter("scan", SCAN_DEVICE_DECODE_FALLBACKS) \
        - decode0[1]
    step["device_decode_files"] = files
    step["device_decode_fallbacks"] = fallbacks
    if fallbacks or not files:
        raise SmokeFailure(f"{label}: device_decode_files={files} "
                           f"device_decode_fallbacks={fallbacks}")
    ladder = (_counter("compaction", COMPACTION_BUCKET_RETRIES)
              - ladder0[0],
              _counter("compaction", COMPACTION_BUCKET_FALLBACKS)
              - ladder0[1])
    if any(ladder):
        raise SmokeFailure(f"{label}: compaction retries/fallbacks "
                           f"{ladder}")

    # which device programs were built
    programs = {
        "sort.packed": M._merge_fn_packed.cache_info().currsize,
        "sort.full-perm": M._merge_fn.cache_info().currsize,
        "mesh.window": len(mesh_engine._KERNEL_CACHE),
    }
    missing = [k for k, v in programs.items() if not v]
    if missing:
        raise SmokeFailure(f"B: device programs never built: {missing}")
    mesh_devices = sorted(
        {d.id for k in mesh_engine._KERNEL_CACHE.values()
         for d in k.sharding.device_set})
    if mesh_devices != sorted(d.id for d in jax.local_devices()):
        raise SmokeFailure(f"B: mesh kernel sharded over devices "
                           f"{mesh_devices}, not all {n_dev} local ones")
    return {"rows": rows, "runs": RUNS_B, "buckets": buckets,
            "keys": len(want["deduplicate"]["id"]),
            "variants": variants, "programs_built": programs,
            "mesh_devices": mesh_devices}


# ---------------------------------------------------------------------------
# entry
# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    args = _parse_args(argv)
    rehearsal = args.platform == "cpu"
    if rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
    t_start = time.perf_counter()
    try:
        import jax
        import numpy as np

        import paimon_tpu.ops  # noqa: F401  x64 + the compile cache
        from paimon_tpu import native
        from paimon_tpu.ops import merge as M
    except ImportError as e:
        sys.stderr.write(f"chip_smoke: cannot import the system under "
                         f"test: {e!r}\n")
        return 2

    backend = jax.default_backend()
    if backend != "tpu" and not rehearsal:
        sys.stderr.write(
            f"chip_smoke: JAX's default backend is {backend!r}, not "
            f"'tpu'; there is no accelerator to smoke.  (--platform cpu "
            f"--rows 200000 rehearses the script on the CPU.)\n")
        return 3
    if native.load() is None:
        sys.stderr.write("chip_smoke: the native library did not build "
                         "or load (paimon_tpu/native)\n")
        return 4
    dev0 = jax.devices()[0]
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(jax.devices())}
    header = {
        "jax": jax.__version__, "backend": backend, "device": device,
        "local_devices": jax.local_device_count(),
        "compile_cache_dir": jax.config.jax_compilation_cache_dir,
        "JAX_COMPILATION_CACHE_DIR":
            os.environ.get("JAX_COMPILATION_CACHE_DIR"),
        "native_library": native.loaded_path(),
        "rows": args.rows, "rows_default": DEFAULT_ROWS,
        "seed": args.seed, "rehearsal": rehearsal,
    }
    print(f"[chip_smoke] header {json.dumps(header)}", flush=True)

    meter = _CompileMeter(jax)
    rec = _Recorder(M, meter)
    report = dict(header)
    try:
        with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
            snap = meter.snapshot()
            report["phase_a"] = _phase_a(np, rec, tmp, args.rows,
                                         args.seed)
            report["phase_a"].update(meter.since(snap))
            a_steps = len(rec.steps)
            snap = meter.snapshot()
            report["phase_b"] = _phase_b(np, jax, rec, tmp,
                                         max(args.rows // 4, RUNS_B),
                                         args.seed)
            report["phase_b"].update(meter.since(snap))
    except Exception:                       # noqa: BLE001
        # the boundary of the script: report, print NO result, fail
        traceback.print_exc()
        sys.stderr.write("chip_smoke: FAILED\n")
        return 1

    report["default_route_device_merges"] = sum(
        s["merge_paths"]["device"] for s in rec.steps[:a_steps])
    report["link_bytes_per_s"] = M._LINK_BW
    report["peak_device_bytes"] = [
        (d.memory_stats() or {}).get("peak_bytes_in_use")
        for d in jax.local_devices()]
    report["steps"] = rec.steps
    report["smoke_wall_s"] = round(time.perf_counter() - t_start, 1)
    report["timings_are"] = "smoke timings, not metrics"
    report["ok"] = True
    report["claim"] = None
    os.makedirs(OUT_DIR, exist_ok=True)
    with open(os.path.join(OUT_DIR, "chip_smoke.json"), "w") as f:
        json.dump(report, f, indent=1)
    print(f"[chip_smoke] report {json.dumps(report)}", flush=True)
    # the result line: these two keys and no other
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
