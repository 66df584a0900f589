"""Merge-on-read scan of the built, uncompacted table with default
options, one `to_arrow()` per operation.

Timed from `load` to the materialised Arrow table.  Rows are counted on
the input side: the rows the data files hold."""

from __future__ import annotations

from chipbench import reference
from chipbench.operations import _table


def prepare(run):
    _table.build(run)


def before(run, i):
    return run.state["base"]


def operation(run, path):
    from paimon_tpu.table import FileStoreTable
    return FileStoreTable.load(path).to_arrow()


def after(run, i, result):
    """Every result is held to the reference's row count and column
    checksums at once; the first and the latest are kept whole."""
    reference.check_checksum(reference.table_checksum(result),
                             run.state["want_sum"], f"scan {i}")
    run.state.setdefault("first", result)
    run.state["last"] = result
    return run.state["input_rows"]


def warm(run):
    operation(run, before(run, "warm"))


def verify(run):
    for which in ("first", "last"):
        reference.check_equal(reference.columns_of(run.state[which]),
                              run.state["want"], f"{which} scan")
