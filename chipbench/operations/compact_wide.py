"""Full compaction of a clone of a built wide, nullable table, one per
operation: `compact.py`'s operation on `data_wide`'s snapshots, held to
`reference_wide`.

Timed from `compact(full=True)` to the committed snapshot.  Rows are
counted on the input side: the rows the data files hold."""

from __future__ import annotations

import os
import time
from concurrent.futures import ThreadPoolExecutor

from chipbench import data, data_wide, reference_wide
from chipbench.operations._table import BUILD_BATCHES
from chipbench.operations.compact import (  # noqa: F401
    after, before, operation, warm,
)


def _reference(snapshots, key, groups, ungrouped):
    want = reference_wide.merged(snapshots, key, groups, ungrouped)
    return want, reference_wide.checksum(want, key)


def prepare(run):
    """Leaves in `run.state`: `base` (the table's path), `key`, `want`
    (the reference, sorted by key), `want_sum`, and `input_rows`: the
    rows the data files hold, which one full compaction reads."""
    table_cfg = run.config["table"]
    key, groups, ungrouped, _ = data_wide.layout(table_cfg)
    t = time.perf_counter()
    snapshots = data_wide.gen_snapshots(
        run.args.seed, run.data["keys"], run.config["data"]["key_seed"],
        table_cfg, run.config["snapshots"])
    run.setup["generate_s"] = time.perf_counter() - t
    base = os.path.join(run.tmp, "base")
    table = data.create_table(base, table_cfg)
    with ThreadPoolExecutor(max_workers=1) as pool:
        # numpy's gathers release the interpreter lock, so the reference
        # runs beside the build and not after it
        ref = pool.submit(_reference, snapshots, key, groups, ungrouped)
        t = time.perf_counter()
        with data.host_pinned_build():
            for snapshot in snapshots:
                data.write_commit(table,
                                  data_wide.to_arrow(snapshot, table_cfg),
                                  BUILD_BATCHES)
        run.setup["build_s"] = time.perf_counter() - t
        t = time.perf_counter()
        want, want_sum = ref.result()
        run.setup["reference_wait_s"] = time.perf_counter() - t
    run.state.update(base=base, key=key, want=want, want_sum=want_sum,
                     clones=[],
                     input_rows=table.new_scan().plan().row_count,
                     written_rows=sum(len(s[key][0]) for s in snapshots))


def verify(run):
    """The first and the last compacted table equal the reference cell
    for cell; the others by row count and null-aware column checksums."""
    from paimon_tpu.table import FileStoreTable
    clones, key = run.state["clones"], run.state["key"]
    for n, clone in enumerate(clones):
        got = FileStoreTable.load(clone).to_arrow()
        if n in (0, len(clones) - 1):
            reference_wide.check_equal(reference_wide.columns_of(got),
                                       run.state["want"], key,
                                       f"compaction {n}")
        else:
            reference_wide.check_checksum(
                reference_wide.table_checksum(got, key),
                run.state["want_sum"], f"compaction {n}")
