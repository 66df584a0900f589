"""Merge-on-read scan with a projection and a predicate pushed into it:
`scan.py`'s operation with `to_arrow(projection=..., predicate=...)`.

The traffic mix gives the projection and the predicate as
`{"and": [[column, op, literal], ...]}`.  The reference is
`reference.merged(...)` masked by the predicate and cut to the projected
columns, here, in the operation's own file.  Rows are counted on the
input side — every row the data files hold, pruned or not — so the rate
compares with `dedup_scan`'s."""

from __future__ import annotations

import numpy as np

from chipbench import reference
from chipbench.operations import _table

_NUMPY = {"<": np.less, "<=": np.less_equal, ">": np.greater,
          ">=": np.greater_equal, "=": np.equal}
_PREDICATE = {"<": "less_than", "<=": "less_or_equal", ">": "greater_than",
              ">=": "greater_or_equal", "=": "equal"}


def _predicate(spec):
    from paimon_tpu import predicate as P
    return P.and_(*[getattr(P, _PREDICATE[op])(column, literal)
                    for column, op, literal in spec["and"]])


def prepare(run):
    _table.build(run)
    merged = run.state.pop("want")
    mask = np.ones(len(merged["id"]), dtype=bool)
    for column, op, literal in run.traffic["predicate"]["and"]:
        mask &= _NUMPY[op](merged[column], literal)
    want = {c: merged[c][mask] for c in run.traffic["projection"]}
    run.state.update(want=want, want_sum=reference.checksum(want),
                     predicate=_predicate(run.traffic["predicate"]),
                     result_rows=int(mask.sum()))


def before(run, i):
    return run.state["base"]


def operation(run, path):
    from paimon_tpu.table import FileStoreTable
    return FileStoreTable.load(path).to_arrow(
        projection=run.traffic["projection"],
        predicate=run.state["predicate"])


def after(run, i, result):
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
