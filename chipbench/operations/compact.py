"""Full compaction of a clone of the built table, one per operation.

Timed from `compact(full=True)` to the committed snapshot.  Rows are
counted on the input side: the rows the data files hold."""

from __future__ import annotations

import os

from chipbench import data, reference
from chipbench.operations import _table


def prepare(run):
    _table.build(run)
    run.state["clones"] = []


def before(run, i):
    clone = os.path.join(run.tmp, f"clone_{i}")
    data.clone_table(run.state["base"], clone)
    return clone


def operation(run, clone):
    from paimon_tpu.table import FileStoreTable
    if FileStoreTable.load(clone).compact(full=run.traffic["full"]) is None:
        raise RuntimeError("compaction committed nothing")
    return clone


def after(run, i, clone):
    run.state["clones"].append(clone)
    return run.state["input_rows"]


def warm(run):
    operation(run, before(run, "warm"))


def verify(run):
    """The first and the last compacted table equal the reference row
    for row; the others by row count and column checksums."""
    from paimon_tpu.table import FileStoreTable
    clones = run.state["clones"]
    for n, clone in enumerate(clones):
        got = FileStoreTable.load(clone).to_arrow()
        if n in (0, len(clones) - 1):
            reference.check_equal(reference.columns_of(got),
                                  run.state["want"], f"compaction {n}")
        else:
            reference.check_checksum(reference.table_checksum(got),
                                     run.state["want_sum"],
                                     f"compaction {n}")
