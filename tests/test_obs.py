"""Observability plane acceptance tests (ISSUE 5).

The headline test runs a traced 8-way pipelined write + scan, exports
Chrome trace-event JSON, and PARSES it: overlapping IO/decode/merge
(scan) and sort/encode/upload (write) spans from >=2 concurrent worker
threads, with table/bucket attributes — no eyeballing.  The other
tests cover the $metrics/$traces system tables (direct + SQL), the
Prometheus GET /metrics endpoint, option-driven switch sync, the CLI
surface, and the <2% disabled-path overhead bound (micro `obs`).
"""

import json
import os
import re
import subprocess
import sys
import urllib.request

import numpy as np
import pyarrow as pa
import pytest

from paimon_tpu import obs
from paimon_tpu.schema import Schema
from paimon_tpu.table import FileStoreTable
from paimon_tpu.types import BigIntType, DoubleType, VarCharType

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_tracer():
    """Tracing is process-global: save/restore the switches and clear
    the ring around every test so no spans leak across tests."""
    was_tracing = obs.tracing_enabled()
    was_metrics = obs.metrics_enabled()
    ring = obs.collector().max_spans
    obs.collector().clear()
    yield
    (obs.enable_tracing if was_tracing else obs.disable_tracing)()
    obs.set_metrics_enabled(was_metrics)
    obs.collector().resize(ring)     # a test's small ring stays its own
    obs.collector().clear()


def _schema(extra_opts=None):
    opts = {"bucket": "8", "write-only": "true",
            "scan.split.parallelism": "8",
            "write.flush.parallelism": "8"}
    opts.update(extra_opts or {})
    return (Schema.builder()
            .column("id", BigIntType(False))
            .column("v", DoubleType())
            .column("s", VarCharType())
            .primary_key("id")
            .options(opts).build())


def _data(rows, seed):
    rng = np.random.default_rng(seed)
    ids = rng.permutation(rows)
    return pa.table({
        "id": pa.array(ids, pa.int64()),
        "v": pa.array(rng.random(rows), pa.float64()),
        "s": pa.array(np.char.add("payload-", ids.astype(str))),
    })


def _build_traced_table(path, rows=120_000, extra_opts=None):
    """Two overlapping commits (same key range) so every bucket holds
    2 L0 runs and the scan actually merges."""
    table = FileStoreTable.create(path, _schema(extra_opts))
    for seed in (1, 2):
        wb = table.new_batch_write_builder()
        with wb.new_write() as w:
            w.write_arrow(_data(rows, seed))
            wb.new_commit().commit(w.prepare_commit())
    return table


def _x_events(trace):
    return [e for e in trace["traceEvents"] if e.get("ph") == "X"]


def _has_cross_thread_overlap(events):
    evts = sorted(events, key=lambda e: e["ts"])
    for i, a in enumerate(evts):
        for b in evts[i + 1:]:
            if b["ts"] >= a["ts"] + a["dur"]:
                break
            if a["tid"] != b["tid"]:
                return True
    return False


class TestChromeTraceExport:
    def test_traced_pipelined_write_scan_overlap(self, tmp_path):
        """THE acceptance criterion: export -> parse -> assert."""
        obs.enable_tracing()
        table = _build_traced_table(str(tmp_path / "t"))
        out = table.to_arrow()
        assert out.num_rows == 120_000

        trace_path = str(tmp_path / "trace.json")
        obs.export_chrome_trace(trace_path)
        with open(trace_path) as f:
            trace = json.load(f)
        events = _x_events(trace)
        by_name = {}
        for e in events:
            by_name.setdefault(e["name"], []).append(e)

        # -- scan: split admit -> IO -> decode -> merge, per worker ----
        for name in ("scan.admit", "scan.split", "io.read", "decode",
                     "scan.merge"):
            assert by_name.get(name), f"missing {name} spans"
        split_spans = by_name["scan.split"]
        assert len({e["tid"] for e in split_spans}) >= 2, \
            "scan.split spans from fewer than 2 worker threads"
        assert _has_cross_thread_overlap(split_spans), \
            "no two scan.split spans overlapped across workers"
        scan_stage = by_name["io.read"] + by_name["decode"] + \
            by_name["scan.merge"]
        assert _has_cross_thread_overlap(scan_stage), \
            "no cross-thread IO/decode/merge overlap in the scan"
        # table/bucket attributes ride the spans
        attred = [e for e in split_spans
                  if isinstance(e["args"].get("bucket"), int)
                  and e["args"].get("table")]
        assert attred, "scan.split spans carry no table/bucket attrs"
        assert {e["args"]["bucket"] for e in attred} == set(range(8))

        # -- write: sort -> encode -> upload, per bucket actor ---------
        for name in ("write.flush", "write.sort", "encode", "io.upload"):
            assert by_name.get(name), f"missing {name} spans"
        flush_spans = by_name["write.flush"]
        assert len({e["tid"] for e in flush_spans}) >= 2
        assert _has_cross_thread_overlap(flush_spans), \
            "no two write.flush spans overlapped across workers"
        write_stage = by_name["write.sort"] + by_name["encode"] + \
            by_name["io.upload"]
        assert _has_cross_thread_overlap(write_stage), \
            "no cross-thread sort/encode/upload overlap in the write"
        assert {e["args"].get("bucket") for e in flush_spans} \
            >= set(range(8))

        # -- commit: CAS + manifest encode are on the timeline ---------
        assert by_name.get("commit.cas")
        assert by_name.get("commit.manifest_encode")

        # thread tracks are named (Perfetto metadata events)
        meta = [e for e in trace["traceEvents"]
                if e.get("ph") == "M" and e["name"] == "thread_name"]
        names = {e["args"]["name"] for e in meta}
        assert any(n.startswith("paimon-scan") for n in names)
        assert any(n.startswith("paimon-write") for n in names)

    def test_span_nesting_and_ring_bound(self, tmp_path):
        obs.enable_tracing(max_spans=64)
        table = _build_traced_table(str(tmp_path / "t"), rows=20_000)
        table.to_arrow()
        spans = obs.take_spans()
        assert len(spans) <= 64                  # bounded ring
        assert obs.collector().dropped > 0       # and it did evict
        # children recorded parents (io.read nests under scan.split)
        by_id = {s.span_id: s for s in spans}
        nested = [s for s in spans
                  if s.parent_id is not None and s.parent_id in by_id]
        assert any(by_id[s.parent_id].name == "scan.split"
                   for s in nested if s.name in ("io.read", "decode"))


class TestSystemTables:
    def test_metrics_system_table(self, tmp_path):
        table = _build_traced_table(str(tmp_path / "t"), rows=5_000)
        table.to_arrow()
        m = table.system_table("metrics")
        rows = m.to_pylist()
        groups = {r["group"] for r in rows}
        assert {"scan", "write", "commit", "io"} <= groups
        by_key = {(r["group"], r["metric"]): r for r in rows}
        assert by_key[("write", "flushes")]["kind"] == "counter"
        assert by_key[("write", "flushes")]["value"] >= 8
        h = by_key[("io", "read_ms")]
        assert h["kind"] == "histogram" and h["count"] >= 1 \
            and h["p95"] is not None

    def test_traces_system_table(self, tmp_path):
        obs.enable_tracing()
        table = _build_traced_table(str(tmp_path / "t"), rows=5_000)
        table.to_arrow()
        t = table.system_table("traces")
        rows = t.to_pylist()
        assert rows
        names = {r["name"] for r in rows}
        assert "scan.split" in names and "write.flush" in names
        split = [r for r in rows if r["name"] == "scan.split"]
        assert any(r["bucket"] is not None and r["table"]
                   for r in split)
        assert all(r["dur_us"] >= 0 and r["start_us"] > 0
                   for r in rows)
        # empty ring still yields the typed schema
        obs.collector().clear()
        empty = table.system_table("traces")
        assert empty.num_rows == 0
        assert set(t.column_names) == set(empty.column_names)

    def test_sql_executor_metrics_and_traces(self, tmp_path):
        from paimon_tpu.catalog.catalog import Identifier, create_catalog
        from paimon_tpu.sql import SQLContext

        obs.enable_tracing()
        catalog = create_catalog({"warehouse": str(tmp_path / "wh")})
        catalog.create_database("d1", ignore_if_exists=True)
        catalog.create_table(Identifier.parse("d1.t"), _schema())
        ctx = SQLContext(catalog, database="d1")
        ctx.sql("INSERT INTO t VALUES (1, 1.5, 'a'), (2, 2.5, 'b')")
        ctx.sql("SELECT * FROM t")
        m = ctx.sql("SELECT * FROM t$metrics")
        assert m.num_rows > 0
        assert "scan" in set(m.column("group").to_pylist())
        tr = ctx.sql("SELECT * FROM d1.t$traces")
        assert tr.num_rows > 0
        assert "write.flush" in set(tr.column("name").to_pylist())


PROM_SAMPLE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^{}]*\})? -?[0-9.eE+]+$")


class TestPrometheusEndpoint:
    def test_get_metrics_valid_exposition(self, tmp_path):
        from paimon_tpu.metrics import (
            COMPACTION_BUCKET_RETRIES, global_registry,
        )
        from paimon_tpu.service.query_service import KvQueryServer

        table = _build_traced_table(str(tmp_path / "t"), rows=5_000)
        table.to_arrow()
        # compaction counters exist the moment the plane touches them
        table.copy({"write-only": "false"}).compact(full=True)
        global_registry().compaction_metrics() \
            .counter(COMPACTION_BUCKET_RETRIES)

        server = KvQueryServer(table).start()
        try:
            with urllib.request.urlopen(
                    f"{server.address}/metrics", timeout=30) as resp:
                assert resp.status == 200
                assert resp.headers["Content-Type"].startswith(
                    "text/plain")
                body = resp.read().decode()
        finally:
            server.stop()

        lines = [ln for ln in body.splitlines() if ln]
        assert lines
        declared = set()
        for ln in lines:
            if ln.startswith("# TYPE "):
                _, _, rest = ln.partition("# TYPE ")
                fam, kind = rest.rsplit(" ", 1)
                assert kind in ("counter", "gauge", "summary",
                                "histogram"), ln
                declared.add(fam)
            else:
                assert PROM_SAMPLE.match(ln), f"invalid sample: {ln!r}"
        # scan/write/compaction counters are all present
        assert "paimon_scan_pipeline_splits" in declared
        assert "paimon_write_flushes" in declared
        assert any(f.startswith("paimon_compaction_")
                   for f in declared)
        # per-stage latency summaries made it too
        assert "paimon_scan_split_ms" in declared
        assert "paimon_io_read_ms" in declared
        # every sample's family was declared
        for ln in lines:
            if not ln.startswith("#"):
                name = re.split(r"[{ ]", ln, 1)[0]
                base = re.sub(r"_(sum|count|bucket)$", "", name)
                assert name in declared or base in declared, ln

    def test_render_prometheus_escapes_labels(self):
        from paimon_tpu.obs.export import render_prometheus
        rows = [{"group": "scan", "table": 'we"ird\\t', "metric": "c",
                 "kind": "counter", "value": 1}]
        text = render_prometheus(rows)
        assert 'table="we\\"ird\\\\t"' in text

    def test_summary_sum_count_are_cumulative(self):
        """Prometheus _count/_sum must be monotonic: they come from
        the histogram's cumulative totals, not the sliding window
        (which caps at 100 and would make rate() read zero)."""
        from paimon_tpu.metrics import MetricRegistry
        from paimon_tpu.obs.export import render_prometheus

        reg = MetricRegistry()
        h = reg.scan_metrics().histogram("lat_ms")
        for i in range(250):
            h.update(2.0)
        text = render_prometheus(reg.snapshot_rows())
        assert "paimon_scan_lat_ms_count 250" in text
        assert "paimon_scan_lat_ms_sum 500" in text

    def test_histogram_le_buckets_real_exposition(self):
        """Satellite: every histogram additionally exports a REAL
        cumulative `le`-bucket family (`<base>_hist`) so PromQL
        histogram_quantile works fleet-wide — validated line by line:
        fixed shared bounds, monotone cumulative counts, +Inf equals
        _hist_count, and _hist_sum equals the cumulative total."""
        from paimon_tpu.metrics import (
            HISTOGRAM_BUCKET_BOUNDS_MS, MetricRegistry,
        )
        from paimon_tpu.obs.export import render_prometheus

        reg = MetricRegistry()
        h = reg.scan_metrics("t1").histogram("lat_ms")
        values = [0.5, 1.0, 3.0, 30.0, 450.0, 99_999.0]
        for v in values:
            h.update(v)
        text = render_prometheus(reg.snapshot_rows())
        lines = [ln for ln in text.splitlines() if ln]
        assert "# TYPE paimon_scan_lat_ms_hist histogram" in lines

        sample = re.compile(
            r'^paimon_scan_lat_ms_hist_bucket\{table="t1",'
            r'le="([^"]+)"\} (\d+)$')
        buckets = []
        for ln in lines:
            m = sample.match(ln)
            if m:
                buckets.append((m.group(1), int(m.group(2))))
        # one line per shared fixed bound, +Inf last — the IDENTICAL
        # bound set on every replica is what makes sum() aggregation
        # across the fleet legal
        assert [b for b, _ in buckets] == \
            [("%g" % b) for b in HISTOGRAM_BUCKET_BOUNDS_MS] + ["+Inf"]
        counts = [c for _, c in buckets]
        assert counts == sorted(counts), "le counts must be cumulative"
        assert counts[-1] == len(values)
        # le="1" counts 0.5 AND the exactly-1.0 update (le is <=)
        assert counts[0] == 2
        sum_ln = [ln for ln in lines
                  if ln.startswith("paimon_scan_lat_ms_hist_sum")]
        cnt_ln = [ln for ln in lines
                  if ln.startswith("paimon_scan_lat_ms_hist_count")]
        assert float(sum_ln[0].rsplit(" ", 1)[1]) == sum(values)
        assert int(cnt_ln[0].rsplit(" ", 1)[1]) == len(values)
        # the pre-existing summary family is untouched alongside
        assert "# TYPE paimon_scan_lat_ms summary" in lines


class TestSwitches:
    def test_sync_from_options_explicit_wins_absent_leaves(self,
                                                           tmp_path):
        from paimon_tpu.obs.trace import sync_from_options
        from paimon_tpu.options import CoreOptions

        obs.disable_tracing()
        sync_from_options(CoreOptions({"trace.enabled": "true",
                                       "trace.buffer.spans": "32"}))
        assert obs.tracing_enabled()
        assert obs.collector().max_spans == 32
        # absent key leaves the state (an explicit enable_tracing or a
        # traced table must not be reverted by the next untraced one)
        sync_from_options(CoreOptions({"bucket": "1"}))
        assert obs.tracing_enabled()
        # ... and an absent buffer key must NOT resize the ring to the
        # option default (resizing drops collected spans)
        obs.enable_tracing(max_spans=12345)
        sync_from_options(CoreOptions({"trace.enabled": "true"}))
        assert obs.collector().max_spans == 12345
        sync_from_options(CoreOptions({"trace.enabled": "false"}))
        assert not obs.tracing_enabled()
        sync_from_options(CoreOptions({"metrics.enabled": "false"}))
        assert not obs.metrics_enabled()
        sync_from_options(CoreOptions({"metrics.enabled": "true"}))
        assert obs.metrics_enabled()

    def test_table_option_enables_tracing_and_histograms(self,
                                                         tmp_path):
        from paimon_tpu.metrics import global_registry

        obs.disable_tracing()
        table = _build_traced_table(
            str(tmp_path / "t"), rows=5_000,
            extra_opts={"trace.enabled": "true"})
        table.to_arrow()
        assert obs.tracing_enabled()
        names = {s.name for s in obs.take_spans()}
        assert "scan.split" in names and "write.flush" in names
        snap = global_registry().snapshot()
        assert snap["scan"]["split_ms"]["count"] >= 8
        assert snap["write"]["sort_ms"]["count"] >= 8

    def test_unwritable_export_path_never_fails_the_scan(self,
                                                         tmp_path):
        out = os.path.join(str(tmp_path), "missing-dir", "x.json")
        table = _build_traced_table(
            str(tmp_path / "t"), rows=5_000,
            extra_opts={"trace.enabled": "true",
                        "trace.export.path": out})
        with pytest.warns(RuntimeWarning, match="trace export"):
            got = table.to_arrow()       # export fails, scan must not
        assert got.num_rows == 5_000

    def test_chrome_tracks_keyed_by_name_and_ident(self):
        """Dead-pool ident reuse must not fold a scan worker onto a
        write worker's track, and two concurrently-live pools that
        both own a 'paimon-scan_0' must not merge either."""
        from paimon_tpu.obs.export import to_chrome_trace
        from paimon_tpu.obs.trace import Span

        def mk(name, thread, tid):
            return Span(1, None, name, "c", 0.0, 1.0, tid, thread, {})

        trace = to_chrome_trace([
            mk("a", "paimon-write_0", 7),   # pool died,
            mk("b", "paimon-scan_0", 7),    # ident 7 reused
            mk("c", "paimon-scan_0", 9),    # concurrent 2nd scan pool
            mk("d", "paimon-scan_0", 9),    # same live thread
        ])
        ev = {e["name"]: e for e in _x_events(trace)}
        assert ev["a"]["tid"] != ev["b"]["tid"]
        assert ev["b"]["tid"] != ev["c"]["tid"]
        assert ev["c"]["tid"] == ev["d"]["tid"]

    def test_trace_export_path_flushes_on_completion(self, tmp_path):
        out = str(tmp_path / "auto.json")
        _build_traced_table(
            str(tmp_path / "t"), rows=5_000,
            extra_opts={"trace.enabled": "true",
                        "trace.export.path": out}).to_arrow()
        with open(out) as f:
            trace = json.load(f)
        assert any(e["name"] == "scan.split"
                   for e in _x_events(trace))

    def test_metrics_disabled_stops_histograms(self, tmp_path):
        from paimon_tpu.metrics import global_registry

        obs.disable_tracing()
        before = global_registry().snapshot() \
            .get("scan", {}).get("split_ms", {"count": 0})["count"]
        table = _build_traced_table(
            str(tmp_path / "t"), rows=5_000,
            extra_opts={"metrics.enabled": "false"})
        table.to_arrow()
        after = global_registry().snapshot() \
            .get("scan", {}).get("split_ms", {"count": 0})["count"]
        assert after == before


class TestCli:
    def _bootstrap(self, wh):
        from paimon_tpu.cli import main
        assert main(["-w", wh, "db", "create", "d1"]) == 0
        assert main(["-w", wh, "table", "create", "d1.t",
                     "--column", "id:BIGINT NOT NULL",
                     "--column", "v:DOUBLE",
                     "--primary-key", "id",
                     "--option", "bucket=2"]) == 0
        assert main(["-w", wh, "sql",
                     "INSERT INTO d1.t VALUES (1, 1.5), (2, 2.5)"]) == 0

    def test_table_metrics_command(self, tmp_path, capsys):
        from paimon_tpu.cli import main
        wh = str(tmp_path / "wh")
        self._bootstrap(wh)
        capsys.readouterr()
        assert main(["-w", wh, "-f", "json", "table", "metrics",
                     "d1.t"]) == 0
        rows = [json.loads(ln) for ln in
                capsys.readouterr().out.splitlines()]
        assert any(r["group"] == "commit" for r in rows)
        assert main(["-w", wh, "-f", "json", "table", "metrics",
                     "d1.t", "--group", "write"]) == 0
        rows = [json.loads(ln) for ln in
                capsys.readouterr().out.splitlines()]
        assert rows and all(r["group"] == "write" for r in rows)

    def test_read_trace_flag_writes_chrome_json(self, tmp_path,
                                                capsys):
        from paimon_tpu.cli import main
        wh = str(tmp_path / "wh")
        self._bootstrap(wh)
        out = str(tmp_path / "scan-trace.json")
        assert main(["-w", wh, "table", "read", "d1.t",
                     "--trace", out]) == 0
        with open(out) as f:
            trace = json.load(f)
        assert any(e["name"] == "scan.split"
                   for e in _x_events(trace))
        # the scope disabled tracing on the way out
        assert not obs.tracing_enabled()


@pytest.mark.parametrize("entry", ["obs"])
def test_disabled_tracing_overhead_bounded(entry):
    """Tier-1 bound from the issue: the tracing-DISABLED scan hot path
    adds negligible overhead vs a no-instrumentation baseline (micro
    `obs` entry: best-of timings, min overhead over interleaved
    trials).

    Deflaked (ISSUE 12): the true disabled overhead is ~0.1%, but a
    60k-row scan is ~10 ms and under parallel-test load a single noisy
    baseline round used to push the ratio past the old 2% line when
    run with the whole suite (passed in isolation).  Two levers, per
    the issue: more interleaved trials (5 — the min over trials is the
    honest estimate, extra rounds only help) and a 5% tolerance that
    still catches any real per-span regression (a single reintroduced
    hot-path span costs >30%) while sitting far above scheduler
    noise."""
    env = dict(os.environ, MICRO_ROWS="60000", MICRO_RUNS="2",
               OBS_TRIALS="5", JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.micro", entry],
        capture_output=True, text=True, cwd=REPO, env=env, timeout=420)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [json.loads(ln) for ln in proc.stdout.splitlines()]
    by_name = {d["benchmark"]: d for d in lines}
    assert {"obs_scan_noinstr", "obs_scan_trace_disabled",
            "obs_scan_trace_enabled", "obs_scan_fleet",
            "obs_overhead_disabled_pct",
            "obs_overhead_fleet_pct"} <= set(by_name)
    overhead = by_name["obs_overhead_disabled_pct"]["value"]
    assert overhead < 5.0, (
        f"disabled-tracing overhead {overhead}% >= 5% "
        f"(noinstr={by_name['obs_scan_noinstr']['best_seconds']}s, "
        f"disabled="
        f"{by_name['obs_scan_trace_disabled']['best_seconds']}s)")
    # the FULL fleet plane (tracing + flight ring + per-scan spool
    # flush) is the worst case and still must stay in budget: the
    # per-operation cost is one ring append + one buffered file append
    fleet = by_name["obs_overhead_fleet_pct"]["value"]
    assert fleet < 25.0, (
        f"fleet-observability overhead {fleet}% >= 25% "
        f"(noinstr={by_name['obs_scan_noinstr']['best_seconds']}s, "
        f"fleet={by_name['obs_scan_fleet']['best_seconds']}s)")


# -- profiler listener, cross-pool parents, stage spans (ISSUE 26) ----------

def _registry_totals():
    """{(group, metric): (sum or count, samples)} over every table."""
    from paimon_tpu.metrics import global_registry
    out = {}
    for r in global_registry().snapshot_rows():
        if r["kind"] == "histogram":
            value, n = r["total_sum"], r["total_count"]
        elif r["kind"] == "counter":
            value, n = r["value"], r["value"]
        else:
            continue
        key = (r["group"], r["metric"])
        old = out.get(key, (0, 0))
        out[key] = (old[0] + value, old[1] + n)
    return out


def _delta(before, after, group, metric):
    a, b = before.get((group, metric), (0, 0)), after.get((group, metric),
                                                          (0, 0))
    return b[0] - a[0], b[1] - a[1]


def _small_agg_table(path, rows=6_000, commits=3, streamed=True):
    """An aggregation table of `commits` overlapping runs in one bucket;
    `streamed` makes its full compaction take the streamed-window path
    (prefetch thread, merge pool, write pool)."""
    opts = {"bucket": "1", "write-only": "true",
            "merge-engine": "aggregation",
            "fields.v.aggregate-function": "sum"}
    if streamed:
        opts.update({"tpu.merge.stream-threshold-rows": "1000",
                     "tpu.merge.chunk-rows": "2000",
                     "tpu.merge.window-rows": "1500"})
    schema = (Schema.builder().column("id", BigIntType(False))
              .column("v", BigIntType()).primary_key("id")
              .options(opts).build())
    table = FileStoreTable.create(path, schema)
    rng = np.random.default_rng(7)
    for _ in range(commits):
        wb = table.new_batch_write_builder()
        with wb.new_write() as w:
            w.write_arrow(pa.table({
                "id": pa.array(rng.permutation(rows), pa.int64()),
                "v": pa.array(rng.integers(0, 9, rows), pa.int64())}))
            wb.new_commit().commit(w.prepare_commit())
    return FileStoreTable.load(path)


def _host_annotations(trace_dir):
    """{annotation name: count} over every host line of the recorded
    `.xplane.pb`."""
    from jax.profiler import ProfileData
    found = [os.path.join(d, f) for d, _, fs in os.walk(trace_dir)
             for f in fs if f.endswith(".xplane.pb")]
    assert len(found) == 1, found
    names = {}
    for plane in ProfileData.from_file(found[0]).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    names[e.name] = names.get(e.name, 0) + 1
    return names


def test_profiler_session_alone_is_a_listener(tmp_path):
    """No enable_tracing(): an open jax.profiler session puts the
    program's spans on the host plane as `paimon.<name>` and their
    histograms fill as ever; the ring stays empty."""
    import jax
    table = _build_traced_table(str(tmp_path / "t"), rows=5_000)
    assert not obs.tracing_enabled()
    before = _registry_totals()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path / "prof"),
                             profiler_options=options)
    try:
        assert obs.profiler_listening()
        table.to_arrow()
    finally:
        jax.profiler.stop_trace()
    assert not obs.profiler_listening()
    names = _host_annotations(str(tmp_path / "prof"))
    for name in ("paimon.scan.to_arrow", "paimon.scan.split",
                 "paimon.merge.prep", "paimon.decode"):
        assert names.get(name), f"{name} not on any host line: {names}"
    after = _registry_totals()
    for group, metric in (("scan", "split_ms"), ("merge", "prep_ms"),
                          ("io", "decode_ms")):
        assert _delta(before, after, group, metric)[1] > 0, metric
    assert obs.take_spans() == []


def test_no_listener_keeps_the_disabled_path():
    from paimon_tpu.obs import trace as T
    assert not obs.tracing_enabled() and not obs.profiler_listening()
    assert T.span("scan.admit", cat="scan", split=1) is T._NOOP
    grouped = T.span("scan.split", group="scan", metric="split_ms")
    assert type(grouped) is T._MetricSpan
    obs.set_metrics_enabled(False)
    assert T.span("scan.split", group="scan",
                  metric="split_ms") is T._NOOP

    def fn():
        return 1
    assert T.carry(fn) is fn        # nothing to carry, nothing wrapped


def test_carry_records_the_submitters_span_as_parent():
    from concurrent.futures import ThreadPoolExecutor

    from paimon_tpu.obs.trace import carry, span
    obs.enable_tracing()

    def task():
        with span("child"):
            pass
        return obs.current_context_token()

    with ThreadPoolExecutor(max_workers=1) as pool:
        pool.submit(lambda: None).result()      # the worker exists
        with span("submitter"):
            carried = pool.submit(carry(task)).result()
            bare = pool.submit(task).result()
        after = pool.submit(task).result()
    spans = obs.take_spans()
    submitter = next(s for s in spans if s.name == "submitter")
    children = [s for s in spans if s.name == "child"]
    assert [c.parent_id for c in children] == \
        [submitter.span_id, None, None]
    assert carried.endswith(f":{submitter.span_id}")
    # the worker's own context is left as it was found
    assert bare is None and after is None


def _roots(spans):
    """{span id: its root span}, walking parent ids inside the ring."""
    by_id = {s.span_id: s for s in spans}
    out = {}
    for s in spans:
        top = s
        while top.parent_id is not None:
            assert top.parent_id in by_id, \
                f"{s.name}: parent of {top.name} is not in the ring"
            top = by_id[top.parent_id]
        out[s.span_id] = top
    return out


@pytest.mark.parametrize("operation", ["to_arrow", "compact", "commit"])
def test_every_span_walks_back_to_its_operations_root(tmp_path,
                                                      operation):
    table = _small_agg_table(str(tmp_path / "t"))
    obs.enable_tracing(max_spans=50_000)
    if operation == "to_arrow":
        table.copy({"scan.split.parallelism": "4"}).to_arrow()
        allowed = {"scan.to_arrow"}
        threads = ("MainThread",)
    elif operation == "compact":
        assert table.compact(full=True) is not None
        # the group phase (the one task under it), then the commit
        allowed = {"compact.table", "commit"}
        threads = ("MainThread", "paimon-prefetch-pump",
                   "ThreadPoolExecutor")
    else:
        wb = table.new_batch_write_builder()
        with wb.new_write() as w:
            w.write_arrow(pa.table({
                "id": pa.array(np.arange(3_000), pa.int64()),
                "v": pa.array(np.ones(3_000, np.int64))}))
            wb.new_commit().commit(w.prepare_commit())
        allowed = {"write.batch", "write.prepare", "write.commit"}
        threads = ("MainThread", "paimon-write-prep", "paimon-write")
    spans = obs.take_spans()
    roots = _roots(spans)
    assert {r.name for r in roots.values()} == allowed
    assert all(r.thread == "MainThread" for r in roots.values())
    seen = {s.thread.split("_")[0].split("-0")[0] for s in spans}
    for prefix in threads:
        assert any(t.startswith(prefix) for t in seen), (prefix, seen)


def test_compact_table_is_the_root_of_every_task_across_the_pool(
        tmp_path, monkeypatch):
    """Eight buckets on eight cores: `compact.table` on the calling
    thread is the root, every `compact.task` its child on a
    `paimon-compact` worker (through `carry`), the main thread's waits
    are `wait` leaves, and the most tasks in flight is at least two."""
    from paimon_tpu.metrics import global_registry
    monkeypatch.setattr(os, "cpu_count", lambda: 8)
    table = _build_traced_table(str(tmp_path / "t"), rows=8_000)
    before = _registry_totals()
    obs.enable_tracing(max_spans=50_000)
    assert table.compact(full=True) is not None
    spans = obs.take_spans()
    roots = _roots(spans)
    assert {r.name for r in roots.values()} == {"compact.table", "commit"}
    top = next(s for s in spans if s.name == "compact.table")
    assert top.thread == "MainThread"
    assert (top.attrs["groups"], top.attrs["workers"]) == (8, 8)
    assert top.attrs["rows"] == 16_000
    tasks = [s for s in spans if s.name == "compact.task"]
    assert len(tasks) == 8
    assert all(t.parent_id == top.span_id for t in tasks)
    assert all(t.thread.startswith("paimon-compact") for t in tasks)
    assert sorted(t.attrs["bucket"] for t in tasks) == list(range(8))
    admits = [s for s in spans if s.name == "compact.admit"]
    assert len(admits) == 8
    assert all(a.parent_id == top.span_id and a.thread == "MainThread"
               for a in admits)
    waits = [s for s in spans if s.name == "wait"
             and s.attrs["what"] == "compaction task"]
    assert len(waits) == 8
    assert all(w.parent_id == top.span_id and w.thread == "MainThread"
               for w in waits)
    after = _registry_totals()
    assert _delta(before, after, "compaction", "table_ms")[1] == 1
    assert _delta(before, after, "compaction", "duration_ms")[1] == 8
    peak = global_registry().group("compaction") \
        .gauge("concurrent_tasks_peak").value
    assert 2 <= peak <= 8


def test_compact_table_feeds_its_sinks_with_tracing_off(tmp_path,
                                                        monkeypatch):
    """No ring, no profiler: `compaction` / `table_ms` still takes one
    sample a call and the gauge the call's peak; one group runs on the
    calling thread and reads 1."""
    from paimon_tpu.metrics import global_registry
    monkeypatch.setattr(os, "cpu_count", lambda: 8)
    assert not obs.tracing_enabled() and not obs.profiler_listening()
    gauge = global_registry().group("compaction") \
        .gauge("concurrent_tasks_peak")
    many = _build_traced_table(str(tmp_path / "t"), rows=4_000)
    before = _registry_totals()
    assert many.compact(full=True) is not None
    mid = _registry_totals()
    total, samples = _delta(before, mid, "compaction", "table_ms")
    assert samples == 1 and total > 0
    assert 2 <= gauge.value <= 8
    one = _small_agg_table(str(tmp_path / "a"), streamed=False)
    assert one.compact(full=True) is not None
    assert _delta(mid, _registry_totals(), "compaction",
                  "table_ms")[1] == 1
    assert gauge.value == 1
    assert obs.take_spans() == []


@pytest.mark.parametrize("route", ["host", "device"])
def test_stage_histograms_fill_and_bytes_only_on_the_device_route(
        tmp_path, monkeypatch, route):
    """A tiny write -> compact -> scan leaves time in every stage
    histogram the benchmark reads and a span for every other stage;
    bytes cross the link only when a merge took the device route."""
    monkeypatch.delenv("PAIMON_FORCE_HOST_SORT", raising=False)
    if route == "device":
        monkeypatch.setenv("PAIMON_FORCE_DEVICE_SORT", "1")
    else:
        monkeypatch.delenv("PAIMON_FORCE_DEVICE_SORT", raising=False)
    before = _registry_totals()
    obs.enable_tracing(max_spans=50_000)
    table = _small_agg_table(str(tmp_path / "t"))
    dedup = _build_traced_table(str(tmp_path / "d"), rows=2_000)
    assert table.compact(full=True) is not None
    table.to_arrow()
    dedup.to_arrow()
    spans = obs.take_spans()
    after = _registry_totals()
    for group, metric in (("merge", "prep_ms"), ("merge", "agg_ms"),
                          ("merge", "gather_ms"), ("merge", "gather_bytes"),
                          ("write", "route_ms"), ("io", "decode_ms")):
        total, samples = _delta(before, after, group, metric)
        assert samples > 0 and total > 0, (group, metric)
    # stages no metric reads are spans only: no histogram, no counter
    for group, metric in (("merge", "h2d_bytes"), ("compaction", "wait_ms"),
                          ("compaction", "cut_ms"),
                          ("scan", "assemble_ms")):
        assert (group, metric) not in after, (group, metric)
    names = {s.name for s in spans}
    assert {"merge.gather", "merge.cut", "compact.window",
            "scan.assemble", "wait"} <= names
    waits = {s.attrs["what"] for s in spans if s.name == "wait"}
    assert {"compaction prefetch", "compaction merge window",
            "compaction file write"} <= waits
    device = [s for s in spans if s.name == "merge.device"]
    device_ms = _delta(before, after, "merge", "device_ms")
    if route == "device":
        assert device and device_ms[1] == len(device)
        for s in device:
            assert s.attrs["h2d_bytes"] > 0 and s.attrs["d2h_bytes"] > 0
            # whole padded operands of uint32
            assert s.attrs["h2d_bytes"] % 4096 == 0
    else:
        assert not device and device_ms[1] == 0
        assert "merge.host" in names
    # `merge.host` names a sink since PR 36: a sample a host-route merge
    hosts = [s for s in spans if s.name == "merge.host"]
    assert _delta(before, after, "merge", "host_ms")[1] == len(hosts)


def _small_partial_update_table(path, rows=3_000, commits=3):
    """A partial-update table with one sequence group (`ts` over `a`,
    `b`) and one ungrouped column, `commits` runs of every key."""
    schema = (Schema.builder().column("id", BigIntType(False))
              .column("ts", BigIntType()).column("a", BigIntType())
              .column("b", BigIntType()).column("u", BigIntType())
              .primary_key("id")
              .options({"bucket": "1", "write-only": "true",
                        "merge-engine": "partial-update",
                        "fields.ts.sequence-group": "a,b"}).build())
    table = FileStoreTable.create(path, schema)
    rng = np.random.default_rng(11)
    for _ in range(commits):
        wb = table.new_batch_write_builder()
        with wb.new_write() as w:
            w.write_arrow(pa.table({
                "id": pa.array(rng.permutation(rows), pa.int64()),
                **{c: pa.array(rng.integers(0, 50, rows), pa.int64(),
                               mask=rng.random(rows) < 0.2)
                   for c in ("ts", "a", "b", "u")}}))
            wb.new_commit().commit(w.prepare_commit())
    return FileStoreTable.load(path)


@pytest.mark.parametrize("engine", ["partial-update", "aggregation"])
def test_agg_select_spans_the_selections_and_not_the_reductions(
        tmp_path, engine):
    """`agg.select` sits under `agg.reduce` around the sequence-group
    resolution and each order-based selection, with the reductions'
    `agg.device` spans inside it; `sum` / `max` columns open none."""
    if engine == "partial-update":
        table = _small_partial_update_table(str(tmp_path / "t"))
    else:
        table = _small_agg_table(str(tmp_path / "t"), streamed=False)
    before = _registry_totals()
    obs.enable_tracing(max_spans=50_000)
    assert table.compact(full=True) is not None
    spans = obs.take_spans()
    by_id = {s.span_id: s for s in spans}
    selects = [s for s in spans if s.name == "agg.select"]
    total, samples = _delta(before, _registry_totals(), "merge",
                            "select_ms")
    assert samples == len(selects)
    if engine == "aggregation":
        assert not selects and "agg.reduce" in {s.name for s in spans}
        return
    assert total > 0
    assert all(by_id[s.parent_id].name == "agg.reduce" for s in selects)
    # one resolution of the one group (two members), one selection for `u`
    assert sorted((s.attrs["groups"], s.attrs["columns"])
                  for s in selects) == [(0, 1), (1, 2)]
    assert all(s.attrs["rows"] == 9_000 for s in selects)
    inside = {}
    for s in spans:
        if s.name == "agg.device":
            inside[by_id[s.parent_id].name] = \
                inside.get(by_id[s.parent_id].name, 0) + 1
    # ts maximum + last position for the group, last position for `u`
    assert inside == {"agg.select": 3}


def test_gather_times_and_counts_its_bytes_with_tracing_off():
    """`merge.gather` names a sink, so it is timed without a listener,
    and counts the buffer bytes of the table it took."""
    from paimon_tpu.ops.merge import gather
    table = pa.table({"k": pa.array(np.arange(1_000), pa.int64()),
                      "v": pa.array(np.arange(1_000, dtype=np.float64),
                                    mask=np.arange(1_000) % 3 == 0),
                      "s": pa.array([str(i) for i in range(1_000)])})
    indices = np.arange(0, 1_000, 2)[::-1].copy()
    assert not obs.tracing_enabled()
    before = _registry_totals()
    taken = gather(table, indices)
    after = _registry_totals()
    assert taken.equals(table.take(pa.array(indices)))
    assert _delta(before, after, "merge", "gather_ms")[1] == 1
    assert _delta(before, after, "merge", "gather_bytes")[0] == taken.nbytes
    obs.enable_tracing()
    gather(table, indices)
    (sp,) = [s for s in obs.take_spans() if s.name == "merge.gather"]
    assert sp.attrs == {"rows": 500, "columns": 3, "bytes": taken.nbytes}
    obs.disable_tracing()
    obs.set_metrics_enabled(False)
    gather(table, indices)
    # the traced call's bytes, and no more: metrics off counts nothing
    assert _delta(after, _registry_totals(), "merge",
                  "gather_bytes")[0] == taken.nbytes


@pytest.mark.parametrize("engine", ["partial-update", "aggregation"])
def test_the_epilogue_gathers_the_winners_and_not_the_window(engine,
                                                            monkeypatch):
    """`merge` / `gather_bytes` says how far the epilogue engages: a
    partial-update merge of S snapshots takes a fifth of the window's
    rows plus a few one-column views, an aggregation no more than the
    window; every take is a `merge.gather` leaf under `agg.reduce`, and
    a segment with no qualifying row is null through a null index."""
    from paimon_tpu.ops import agg
    from paimon_tpu.ops.merge import KIND_COL, SEQ_COL
    from paimon_tpu.options import CoreOptions
    from paimon_tpu.schema.table_schema import TableSchema
    snapshots, keys = 5, 2_000
    members = [f"m{i:02d}" for i in range(12)]
    if engine == "partial-update":
        options = {"fields.ts.sequence-group": ",".join(members)}
    else:
        options = {f"fields.{c}.aggregate-function": "sum" for c in members}
        options["fields.ts.aggregate-function"] = "max"
    builder = Schema.builder().column("id", BigIntType(False))
    for name in ["ts"] + members + ["u"]:
        builder = builder.column(name, BigIntType())
    schema = TableSchema.from_schema(0, builder.primary_key("id").options(
        {"bucket": "1", "merge-engine": engine, **options}).build())
    rng = np.random.default_rng(5)
    ids = pa.array(np.arange(keys), pa.int64())
    dead = np.arange(keys) == 7         # a key whose `ts` is never set
    runs = [pa.table({
        "_KEY_id": ids,
        SEQ_COL: pa.array(np.arange(keys) + s * keys, pa.int64()),
        KIND_COL: pa.array(np.zeros(keys), pa.int8()),
        "id": ids,
        **{c: pa.array(rng.integers(0, 1 << 30, keys), pa.int64(),
                       mask=(rng.random(keys) < 0.1) | (dead & (c == "ts")))
           for c in ["ts"] + members + ["u"]}}) for s in range(snapshots)]
    window_bytes = pa.concat_tables(runs).nbytes
    taken_at = []
    real_gather = agg.gather
    monkeypatch.setattr(agg, "gather", lambda table, indices: (
        taken_at.append(indices), real_gather(table, indices))[1])
    before = _registry_totals()
    obs.enable_tracing(max_spans=10_000)
    out = agg.merge_runs_agg(runs, ["_KEY_id"], schema,
                             CoreOptions(schema.options))
    spans = obs.take_spans()
    taken = _delta(before, _registry_totals(), "merge", "gather_bytes")[0]
    assert out.num_rows == keys
    if engine == "partial-update":
        assert 0 < taken <= (1 / snapshots + 0.15) * window_bytes
        # key 7: no row qualifies, so the group's take has one null
        # index and its columns are null there, `ts` among them
        assert [i.null_count for i in taken_at] == [0, 1, 0]
        assert out.slice(7, 1).select(["ts"] + members).to_pylist() == \
            [dict.fromkeys(["ts"] + members)]
        assert out.slice(7, 1).column("u").null_count == 0
    else:
        assert 0 < taken <= window_bytes
    by_id = {s.span_id: s for s in spans}
    gathers = [s for s in spans if s.name == "merge.gather"]
    assert sum(s.attrs["bytes"] for s in gathers) == taken
    assert all(by_id[s.parent_id].name == "agg.reduce" for s in gathers)
    assert not {s.parent_id for s in spans} & {s.span_id for s in gathers}
    winners = sorted(s.attrs["columns"] for s in gathers
                     if s.attrs["rows"] == keys)
    views = [s for s in gathers if s.attrs["rows"] == snapshots * keys]
    assert len(winners) + len(views) == len(gathers)
    assert all(s.attrs["columns"] == 1 for s in views)
    # keys + sequence + kind; the group (ts + members); `u`
    assert winners == ([1, 4, 13] if engine == "partial-update"
                       else [1, 4])


def test_streamed_decode_has_the_decode_span(tmp_path):
    """`read_batches` — the compaction's streamed decode — records the
    `decode` span per batch, the name `read`'s span has."""
    from paimon_tpu.format import get_format
    from paimon_tpu.fs import LocalFileIO
    path = str(tmp_path / "f.parquet")
    io = LocalFileIO()
    get_format("parquet").create_writer("zstd", {}).write(
        io, path, pa.table({"a": pa.array(np.arange(5_000))}))
    obs.enable_tracing()
    before = _registry_totals()
    batches = list(get_format("parquet").create_reader().read_batches(
        io, path, batch_rows=2_000))
    assert sum(b.num_rows for b in batches) == 5_000
    decodes = [s for s in obs.take_spans() if s.name == "decode"]
    assert len(decodes) >= len(batches) == 3
    assert _delta(before, _registry_totals(), "io", "decode_ms")[1] \
        >= 3


def test_task_and_commit_durations_keep_one_sample_each(tmp_path):
    table = _small_agg_table(str(tmp_path / "t"), streamed=False)
    before = _registry_totals()
    assert table.compact(full=True) is not None
    after = _registry_totals()
    # one bucket, one task; its result is one commit
    assert _delta(before, after, "compaction", "duration_ms")[1] == 1
    assert _delta(before, after, "compaction", "tasks")[0] == 1
    assert _delta(before, after, "commit", "duration_ms")[1] == 1
    assert _delta(before, after, "commit", "commits")[0] == 1
    wb = table.new_batch_write_builder()
    with wb.new_write() as w:
        w.write_arrow(pa.table({"id": pa.array([1], pa.int64()),
                                "v": pa.array([1], pa.int64())}))
        wb.new_commit().commit(w.prepare_commit())
    assert _delta(after, _registry_totals(), "commit",
                  "duration_ms")[1] == 1


# -- leaves under the envelopes, round trips in flight (ISSUE 36) -----------

def _flush_one_commit(path):
    table = FileStoreTable.create(path, _schema({"bucket": "1"}))
    wb = table.new_batch_write_builder()
    with wb.new_write() as w:
        w.write_arrow(_data(3_000, 1))
        wb.new_commit().commit(w.prepare_commit())


# case: (the operation, the leaf, the envelope above it, the root,
#        attrs the leaf carries)
_LEAF_CASES = {
    "scan:merge.winners": ("scan", "merge.winners", "scan.merge",
                           "scan.to_arrow", {"route": "host"}),
    "scan:wait": ("scan", "wait", "scan.to_arrow", "scan.to_arrow",
                  {"what": "scan split"}),
    "flush:write.build": ("flush", "write.build", "write.sort",
                          "write.prepare", {"rows": 3_000}),
    "compaction:file.stats": ("compaction", "file.stats", "compact.task",
                              "compact.table", {"columns": 4}),
    "agg_compaction:agg.mask": ("agg_compaction", "agg.mask", "agg.reduce",
                                "compact.table", {"column": "v"}),
    "mesh_compaction:merge.winners": ("mesh_compaction", "merge.winners",
                                      "compact.task", "compact.table",
                                      {"route": "mesh"}),
}


@pytest.mark.parametrize("case", sorted(_LEAF_CASES))
def test_new_leaves_lie_under_their_envelopes_and_have_no_child(
        tmp_path, monkeypatch, case):
    """Each leaf of ISSUE 36 is recorded by the operation that runs its
    code, has no span inside it, lies under the envelope whose own time
    it takes, and walks back to the operation's root."""
    operation, leaf, envelope, root, attrs = _LEAF_CASES[case]
    monkeypatch.delenv("PAIMON_FORCE_DEVICE_SORT", raising=False)
    path = str(tmp_path / "t")
    if operation == "flush":
        obs.enable_tracing(max_spans=50_000)
        _flush_one_commit(path)
    elif operation == "agg_compaction":
        table = _small_agg_table(path, streamed=False)
        obs.enable_tracing(max_spans=50_000)
        assert table.compact(full=True) is not None
    else:
        table = _build_traced_table(path, rows=4_000)
        obs.enable_tracing(max_spans=50_000)
        if operation == "scan":
            table.to_arrow()
        else:
            if operation == "mesh_compaction":
                table = table.copy({"tpu.mesh.compact": "true"})
            assert table.compact(full=True) is not None
    spans = obs.take_spans()
    by_id = {s.span_id: s for s in spans}
    parents = {s.parent_id for s in spans}
    found = [s for s in spans if s.name == leaf
             and all(s.attrs.get(k) == v for k, v in attrs.items())]
    assert found, (leaf, attrs, sorted({s.name for s in spans}))
    roots = _roots(spans)
    for s in found:
        assert s.span_id not in parents, f"{leaf} has a child"
        above, up = [], s
        while up.parent_id is not None:
            up = by_id[up.parent_id]
            above.append(up.name)
        assert envelope in above, (leaf, above)
        assert roots[s.span_id].name == root
    if leaf == "merge.winners":
        assert all(s.attrs["rows"] > 0 for s in found)
        assert any(s.attrs.get("winners", 0) > 0 for s in found)


@pytest.mark.parametrize("leaf,roots", [
    ("io.open", {"scan.to_arrow"}),
    ("write.buffer", {"write.batch", "write.prepare"}),
    ("compact.live", {"compact.table"})])
def test_leaves_given_after_the_first_self_times(tmp_path, leaf, roots):
    """`io.open` (between `io.read` and `decode`), `write.buffer` (the
    caller thread's appends and the detached payload) and `compact.live`
    (the merged state's retract filter) name no sink: spans only, leaves,
    under their operation's root."""
    from paimon_tpu.obs import trace as T
    assert T.span(leaf, cat="io") is T._NOOP        # no listener, no cost
    path = str(tmp_path / "t")
    if leaf == "write.buffer":
        obs.enable_tracing(max_spans=50_000)
        _flush_one_commit(path)
    else:
        table = _small_agg_table(path, streamed=False)
        obs.enable_tracing(max_spans=50_000)
        if leaf == "io.open":
            table.to_arrow()
        else:
            assert table.compact(full=True) is not None
    spans = obs.take_spans()
    found = [s for s in spans if s.name == leaf]
    assert found, sorted({s.name for s in spans})
    parents = {s.parent_id for s in spans}
    tops = _roots(spans)
    assert not {s.span_id for s in found} & parents
    assert {tops[s.span_id].name for s in found} <= roots
    assert all(s.attrs.get("rows", s.attrs.get("bytes", 0)) > 0
               for s in found)


def test_new_sinks_and_counters_move_with_tracing_off(tmp_path,
                                                      monkeypatch):
    """No ring, no profiler: the five new histograms take their samples
    and the four new counters count; with the metrics off all stand
    still."""
    from paimon_tpu.ops import merge as M
    monkeypatch.delenv("PAIMON_FORCE_HOST_SORT", raising=False)
    assert not obs.tracing_enabled() and not obs.profiler_listening()
    sinks = (("merge", "winners_ms"), ("io", "stats_ms"),
             ("write", "build_ms"), ("merge", "mask_ms"),
             ("merge", "host_ms"))
    counters = (("merge", "device_trips"), ("merge", "device_inflight_sum"),
                ("merge", "device_rows"), ("write", "route_rows"))

    def run(tag):
        # host route (the cpu backend's own): a flush, an aggregation
        # compaction, a merging scan; then the scan's merges on the
        # device programs
        monkeypatch.delenv("PAIMON_FORCE_DEVICE_SORT", raising=False)
        agg = _small_agg_table(str(tmp_path / f"a{tag}"), streamed=False)
        assert agg.compact(full=True) is not None
        dedup = _build_traced_table(str(tmp_path / f"d{tag}"), rows=2_000)
        dedup.to_arrow()
        monkeypatch.setenv("PAIMON_FORCE_DEVICE_SORT", "1")
        dedup.to_arrow()

    before = _registry_totals()
    run("on")
    after = _registry_totals()
    for group, metric in sinks:
        total, samples = _delta(before, after, group, metric)
        assert samples > 0 and total > 0, (group, metric)
    moved = {c: _delta(before, after, *c)[0] for c in counters}
    assert all(v > 0 for v in moved.values()), moved
    # one merge a bucket, each alone or beside the other workers', and
    # the aggregation's sum and its validity maximum (`agg.device`):
    # round trips too, which count no merge rows
    trips = moved[("merge", "device_trips")]
    assert trips == 8 + 2
    assert trips <= moved[("merge", "device_inflight_sum")] <= 8 * trips
    assert moved[("merge", "device_rows")] == 4_000
    # every row a `write.route` handled: three commits and two
    assert moved[("write", "route_rows")] == 3 * 6_000 + 2 * 2_000
    assert M._TRIPS_OPEN == 0
    obs.set_metrics_enabled(False)
    run("off")
    still = _registry_totals()
    for key in sinks + counters:
        assert _delta(after, still, *key) == (0, 0), key
    assert M._TRIPS_OPEN == 0


def test_round_trips_in_flight_are_counted_and_given_back(monkeypatch):
    """Four merges at once on the device programs: four trips, each
    counting those open as it opened (itself included), and the count of
    open round trips back at zero — after an exception inside the span
    too."""
    import threading

    from paimon_tpu.ops import merge as M
    monkeypatch.delenv("PAIMON_FORCE_HOST_SORT", raising=False)
    monkeypatch.setenv("PAIMON_FORCE_DEVICE_SORT", "1")
    rng = np.random.default_rng(3)
    n = 5_000
    lanes = rng.integers(0, 1 << 10, (n, 2)).astype(np.uint32)
    seq = np.arange(n, dtype=np.int64)
    M.device_sorted_winners(lanes, seq, winners_only=True)   # compiled
    barrier = threading.Barrier(4)
    results, errors = [], []

    def merge():
        try:
            barrier.wait(timeout=60)
            results.append(M.device_sorted_winners(lanes, seq,
                                                   winners_only=True))
        except Exception as e:              # noqa: BLE001
            errors.append(e)

    obs.enable_tracing()
    before = _registry_totals()
    threads = [threading.Thread(target=merge) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    after = _registry_totals()
    assert not errors and len(results) == 4
    assert _delta(before, after, "merge", "device_trips")[0] == 4
    assert 4 <= _delta(before, after, "merge",
                       "device_inflight_sum")[0] <= 16
    assert _delta(before, after, "merge", "device_rows")[0] == 4 * n
    trips = [s for s in obs.take_spans() if s.name == "merge.device"]
    assert sorted(s.attrs["inflight"] for s in trips)[0] == 1
    assert sum(s.attrs["inflight"] for s in trips) == \
        _delta(before, after, "merge", "device_inflight_sum")[0]
    assert M._TRIPS_OPEN == 0
    with pytest.raises(RuntimeError):
        with M.device_span("packed", 1, 1024, 0, 0) as sp:
            assert sp.attrs["inflight"] == 1 and M._TRIPS_OPEN == 1
            with M.device_trip("agg.device", rows=1) as inner:
                assert inner.attrs["inflight"] == 2
            raise RuntimeError("inside the round trip")
    assert M._TRIPS_OPEN == 0
    (failed,) = [s for s in obs.take_spans() if s.name == "merge.device"
                 and s.attrs.get("error")]
    assert failed.attrs["error"] == "RuntimeError"
