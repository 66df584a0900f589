"""TPC-H LINEITEM as a primary-key table (`tpch-lineitem-pk`) under Q1
and Q6: projection, filter and the aggregate pushed below the
merge-on-read merge (ops/scan_agg.py) equal the plain reference
(chipbench/reference_tpch.py) exactly — on the host route and on the
device route (JAX on CPU), with and without the refresh commit's deletes,
one bucket and eight — and what the pushdown cannot express returns what
the materialising path returns."""

import decimal
import json
import os

import pyarrow as pa
import pytest

from chipbench import data_tpch, reference_tpch
from paimon_tpu import create_catalog
from paimon_tpu.ops import merge as M
from paimon_tpu.ops import scan_agg
from paimon_tpu.sql.executor import SQLContext
from paimon_tpu.sql.parser import parse

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "chipbench", "configs",
                       "tpch-lineitem-pk.json")) as f:
    CONFIG = json.load(f)
QUERIES = CONFIG["queries"]
DATA = {**CONFIG["data"], **CONFIG["rehearsal_data"]}


def _build(tmp_path, buckets, deletes, seed=7, table_cfg=None):
    commits = data_tpch.gen_commits(seed, DATA)
    if not deletes:
        commits = commits[:-1]
    catalog = create_catalog({"warehouse": str(tmp_path / "wh")})
    table = data_tpch.create_table(
        catalog, "tpch.lineitem",
        table_cfg or {**CONFIG["table"], "buckets": buckets})
    for c in commits:
        wb = table.new_batch_write_builder()
        with wb.new_write() as w:
            w.write_arrow(data_tpch.to_arrow(c))
            wb.new_commit().commit(w.prepare_commit())
    return SQLContext(catalog, "tpch"), reference_tpch.live_rows(commits)


def _materialised(ctx, sql, monkeypatch):
    """The same statement with the pushdown refused."""
    with monkeypatch.context() as m:
        m.setattr(SQLContext, "_plan_pushed_aggregate",
                  lambda self, s: (None, "refused by the test"))
        return ctx.sql(sql)


@pytest.fixture(params=["host", "device"])
def route(request, monkeypatch):
    monkeypatch.setenv("PAIMON_FORCE_DEVICE_SORT" if request.param ==
                       "device" else "PAIMON_FORCE_HOST_SORT", "1")
    return request.param


@pytest.mark.parametrize("query", ["q1", "q6"])
@pytest.mark.parametrize("deletes", [True, False],
                         ids=["refresh", "load-only"])
@pytest.mark.parametrize("buckets", [1, 8])
def test_query_equals_reference(tmp_path, route, buckets, deletes, query,
                                monkeypatch):
    ctx, rows = _build(tmp_path, buckets, deletes)
    before = dict(M.PATH_COUNTS)
    plan = {}
    got = ctx._exec_select(parse(QUERIES[query]["sql"]), plan)
    assert plan["aggregate"] is not None, plan
    want = getattr(reference_tpch, query)(rows, QUERIES[query]["params"])
    reference_tpch.check(got, want, reference_tpch.SCALES[query], query)
    moved = {k: M.PATH_COUNTS[k] - before[k] for k in before}
    if route == "device":
        assert moved["device"] == buckets and not moved["host"]
    else:
        assert moved["device"] == 0
    # and the materialising path gives the same table, types included
    assert _materialised(ctx, QUERIES[query]["sql"], monkeypatch) \
        .equals(got)


def test_eight_buckets_partials_add_up_to_one_bucket(tmp_path):
    one, _ = _build(tmp_path / "one", 1, True)
    eight, _ = _build(tmp_path / "eight", 8, True)
    for q in QUERIES.values():
        assert one.sql(q["sql"]).equals(eight.sql(q["sql"]))


def test_deleted_orders_contribute_nothing(tmp_path):
    ctx, rows = _build(tmp_path, 8, True)
    loaded, all_rows = _build(tmp_path / "load", 8, False)
    n = ctx.sql("SELECT count(*) AS n FROM lineitem").column("n")[0].as_py()
    assert n == len(rows["l_quantity"])
    commits = data_tpch.gen_commits(7, DATA)
    deleted = int((commits[-1]["kind"] == data_tpch.KIND_DELETE).sum())
    inserted = len(commits[-1]["kind"]) - deleted
    assert n == len(all_rows["l_quantity"]) + inserted - deleted


DECLINED = {
    "double aggregate": "SELECT l_returnflag, sum(l_quantity * 1.5) AS x "
    "FROM lineitem GROUP BY l_returnflag ORDER BY l_returnflag",
    "integer average": "SELECT avg(l_linenumber) AS x FROM lineitem",
    "count distinct": "SELECT count(DISTINCT l_suppkey) AS x FROM lineitem",
    "join": "SELECT count(*) AS n FROM lineitem a JOIN lineitem b ON "
    "a.l_orderkey = b.l_orderkey AND a.l_linenumber = b.l_linenumber "
    "WHERE a.l_quantity < 3",
    "string filter": "SELECT sum(l_quantity) AS x FROM lineitem "
    "WHERE l_shipmode = 'RAIL'",
    "group by expression": "SELECT l_linenumber + 1 AS k, count(*) AS n "
    "FROM lineitem GROUP BY l_linenumber + 1 ORDER BY k",
    # predicate leaves other than comparisons and BETWEEN materialise
    "not": "SELECT count(l_tax) AS n FROM lineitem "
    "WHERE NOT l_discount = 0.05",
    "in list": "SELECT sum(l_quantity) AS x FROM lineitem "
    "WHERE l_linenumber IN (6, 7)",
    "not equal": "SELECT count(*) AS n FROM lineitem WHERE l_tax <> 0.03",
    "is null": "SELECT count(*) AS n FROM lineitem WHERE l_tax IS NULL",
    "threshold off the lane": "SELECT count(*) AS n FROM lineitem "
    "WHERE l_quantity < 24.505",
}
PUSHED = {
    "having and alias": "SELECT l_linestatus AS s, max(l_shipdate) AS d, "
    "min(l_extendedprice) AS lo, count(l_tax) AS n FROM lineitem "
    "WHERE l_quantity BETWEEN 5 AND 30 AND l_discount > 0.05 "
    "GROUP BY s HAVING count(*) > 10 ORDER BY s",
    "integer and date groups": "SELECT l_linenumber, l_shipdate, "
    "sum(l_extendedprice - l_tax * 100) AS x FROM lineitem "
    "WHERE l_shipdate < DATE '1992-03-01' OR l_linenumber >= 6 "
    "GROUP BY l_linenumber, l_shipdate ORDER BY 1, 2 LIMIT 50",
    "no row qualifies": "SELECT sum(l_quantity) AS x, count(*) AS n, "
    "min(l_shipdate) AS d FROM lineitem WHERE l_quantity > 50",
    "no group qualifies": "SELECT l_returnflag, sum(l_quantity) AS x "
    "FROM lineitem WHERE l_quantity > 50 GROUP BY l_returnflag",
    "negative and fraction": "SELECT sum(-l_extendedprice) AS x, "
    "count(*) AS n FROM lineitem WHERE l_quantity < 24.5 "
    "AND l_tax = 0.03",
}


@pytest.mark.parametrize("name", list(DECLINED))
def test_declined_statement_returns_what_it_returned(tmp_path, name,
                                                     monkeypatch):
    ctx, _ = _build(tmp_path, 2, True)
    plan = {}
    got = ctx._exec_select(parse(DECLINED[name]), plan)
    assert plan.get("aggregate") is None
    if name != "join":
        assert plan["aggregate_declined"]
    assert _materialised(ctx, DECLINED[name], monkeypatch).equals(got)


@pytest.mark.parametrize("name", list(PUSHED))
def test_pushed_statement_equals_materialised(tmp_path, route, name,
                                              monkeypatch):
    ctx, _ = _build(tmp_path, 2, True)
    plan = {}
    got = ctx._exec_select(parse(PUSHED[name]), plan)
    assert plan["aggregate"] is not None, plan
    want = _materialised(ctx, PUSHED[name], monkeypatch)
    assert got.schema.equals(want.schema)
    key = [(c, "ascending") for c in got.column_names]
    assert got.sort_by(key).equals(want.sort_by(key))


def test_overflow_guard_declines_at_a_forged_range(tmp_path, monkeypatch):
    """A split whose sums cannot be proved inside int64 is reduced with
    Python integers (route `exact`), to the same numbers."""
    ctx, rows = _build(tmp_path, 2, True)
    want = reference_tpch.q1(rows, QUERIES["q1"]["params"])
    routes = []
    real_span = scan_agg._agg_span
    monkeypatch.setattr(
        scan_agg, "_agg_span",
        lambda rows, groups, aggregates, route:
        routes.append(route) or real_span(rows, groups, aggregates, route))
    # forge a range that no int64 sum holds: the proof must fail
    monkeypatch.setattr(scan_agg, "fits_int64", lambda *a: False)
    got = ctx.sql(QUERIES["q1"]["sql"])
    reference_tpch.check(got, want, reference_tpch.SCALES["q1"], "exact")
    assert "exact" in routes and "device" not in routes


def test_overflow_guard_proves_from_observed_ranges():
    measures = (scan_agg.Measure("sum", ("col", "a")),)
    product = ("*", ("col", "a"), ("col", "a"))
    big = {"a": (0, 10 ** 10)}
    assert scan_agg.fits_int64(measures, (("col", "a"),), big, 10 ** 8)
    assert not scan_agg.fits_int64(measures, (("col", "a"),), big, 10 ** 9)
    assert not scan_agg.fits_int64(measures, (product,), big, 1)
    assert scan_agg.fits_int64(measures, (product,), {"a": (-9, 9)}, 10)


def test_large_values_take_the_exact_route(tmp_path):
    """DECIMAL(18, 2) values near 10^16: a product leaves int64, so the
    split falls to Python integers and still equals Decimal arithmetic."""
    D = decimal.Decimal
    cfg = {"buckets": 1, "primary_key": ["k"], "options": {},
           "columns": [["k", "BIGINT NOT NULL"], ["a", "DECIMAL(18,2)"],
                       ["b", "DECIMAL(18,2)"]]}
    catalog = create_catalog({"warehouse": str(tmp_path / "wh")})
    table = data_tpch.create_table(catalog, "tpch.big", cfg)
    a = [D("9999999999999999.99"), D("-8888888888888888.88"), D("1.01")]
    b = [D("7777777777777777.77"), D("6666666666666666.66"), D("-2.50")]
    wb = table.new_batch_write_builder()
    with wb.new_write() as w:
        w.write_arrow(pa.table({"k": pa.array([1, 2, 3], pa.int64()),
                                "a": pa.array(a, pa.decimal128(18, 2)),
                                "b": pa.array(b, pa.decimal128(18, 2))}))
        wb.new_commit().commit(w.prepare_commit())
    plan = {}
    ctx = SQLContext(catalog, "tpch")
    got = ctx._exec_select(parse(
        "SELECT sum(a * b) AS s, max(a) AS m FROM big"), plan)
    assert plan["aggregate"] is not None
    with decimal.localcontext() as c:
        c.prec = 60
        assert got.column("s")[0].as_py() == sum(x * y for x, y in zip(a, b))
    assert got.column("m")[0].as_py() == max(a)


@pytest.mark.parametrize("engine", ["deduplicate", "first-row"])
def test_string_keys_sharing_a_prefix_stay_distinct(tmp_path, route, engine,
                                                    monkeypatch):
    """A VARCHAR primary key longer than the key lanes' 16-byte prefix:
    `customer-0000000000123` and `...124` encode alike, and the pushed
    aggregate must still count each (the winners are repaired by the
    full key on the host, whatever route is pinned)."""
    cfg = {"buckets": 2, "primary_key": ["id"],
           "options": {"merge-engine": engine, "write-only": "true"},
           "columns": [["id", "VARCHAR(40) NOT NULL"], ["g", "INT"],
                       ["x", "DECIMAL(15,2)"]]}
    catalog = create_catalog({"warehouse": str(tmp_path / "wh")})
    table = data_tpch.create_table(catalog, "tpch.customers", cfg)
    ids = [f"customer-{i:013d}" for i in range(300)]
    for commit in range(3):             # overlapping runs: every key thrice
        wb = table.new_batch_write_builder()
        with wb.new_write() as w:
            w.write_arrow(pa.table({
                "id": pa.array(ids),
                "g": pa.array([i % 3 for i in range(300)], pa.int32()),
                "x": pa.array([decimal.Decimal(i + commit * 1000) / 100
                               for i in range(300)], pa.decimal128(15, 2))}))
            wb.new_commit().commit(w.prepare_commit())
    ctx = SQLContext(catalog, "tpch")
    for sql in ("SELECT count(*) AS n, sum(x) AS s FROM customers",
                "SELECT g, count(*) AS n, sum(x) AS s, max(x) AS m "
                "FROM customers WHERE x >= 0.5 GROUP BY g ORDER BY g"):
        plan = {}
        got = ctx._exec_select(parse(sql), plan)
        assert plan["aggregate"] is not None, plan
        assert _materialised(ctx, sql, monkeypatch).equals(got)
    n, s = (c[0].as_py() for c in ctx.sql(
        "SELECT count(*) AS n, sum(x) AS s FROM customers").columns)
    base = 0 if engine == "first-row" else 2000
    assert n == 300
    assert s == decimal.Decimal(sum(i + base for i in range(300))) / 100


def test_explain_and_plan_show_projection_and_aggregate(tmp_path):
    ctx, _ = _build(tmp_path, 2, False)
    lines = ctx.sql("EXPLAIN " + QUERIES["q1"]["sql"]) \
        .column("plan").to_pylist()
    text = "\n".join(lines)
    assert "pushed projection: ['l_quantity', 'l_extendedprice', " \
        "'l_discount', 'l_tax', 'l_returnflag', 'l_linestatus', " \
        "'l_shipdate']" in text
    assert "pushed aggregate: ScanAggregate(group_by=('l_returnflag', " \
        "'l_linestatus')" in text
    declined = "\n".join(ctx.sql("EXPLAIN " + DECLINED["integer average"])
                         .column("plan").to_pylist())
    assert "pushed aggregate: none (avg() returns double)" in declined
    plan = {}
    out = ctx._exec_select(parse(
        "SELECT l_orderkey, l_comment FROM lineitem WHERE l_tax = 0.08 "
        "ORDER BY l_orderkey LIMIT 3"), plan)
    assert plan["projection"] == ["l_orderkey", "l_tax", "l_comment"]
    assert out.column_names == ["l_orderkey", "l_comment"]
    assert out.num_rows == 3


def test_date_literal_and_decimal_literals(tmp_path):
    ctx, rows = _build(tmp_path, 1, False)
    got = ctx.sql("SELECT l_shipdate, l_discount FROM lineitem WHERE "
                  "l_shipdate = DATE '1995-06-17' AND l_discount >= 0.05")
    keep = (rows["l_shipdate"] == data_tpch.CURRENT_DAY) \
        & (rows["l_discount"] >= 5)
    assert got.num_rows == int(keep.sum())
    assert got.schema.field("l_discount").type == pa.decimal128(15, 2)
