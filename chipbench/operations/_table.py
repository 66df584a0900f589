"""Set-up shared by the kinds that work on a built table: the
configuration's commits written through the program's own write path,
and the numpy reference of their merge, computed beside the build."""

from __future__ import annotations

import os
import time
from concurrent.futures import ThreadPoolExecutor

from chipbench import data, reference


def generate(run):
    d = run.data
    t = time.perf_counter()
    runs = data.gen_runs(run.args.seed, d["rows"], d["runs"],
                         d["key_space"], run.config["data"]["key_seed"])
    run.setup["generate_s"] = time.perf_counter() - t
    return runs


# the build is not under test: handing the writer a commit in pieces lets
# its bucket hashing run on several threads, and the files are the same
BUILD_BATCHES = 8


def _reference(runs, engine):
    want = reference.merged(data.concat(runs), engine)
    return want, reference.checksum(want)


def build(run):
    """Leaves in `run.state`: `base` (the table's path), `want` (the
    reference, sorted by key), `want_sum`, and `input_rows`: the rows
    the data files hold, which one scan or full compaction reads."""
    runs = generate(run)
    base = os.path.join(run.tmp, "base")
    table = data.create_table(base, run.config["table"])
    with ThreadPoolExecutor(max_workers=1) as pool:
        # numpy's sort releases the interpreter lock, so the reference
        # runs beside the build and not after it
        ref = pool.submit(_reference, runs, run.config["table"]["engine"])
        t = time.perf_counter()
        with data.host_pinned_build():
            for r in runs:
                data.write_commit(table, data.to_arrow(r), BUILD_BATCHES)
        run.setup["build_s"] = time.perf_counter() - t
        t = time.perf_counter()
        want, want_sum = ref.result()
        run.setup["reference_wait_s"] = time.perf_counter() - t
    run.state.update(base=base, want=want, want_sum=want_sum,
                     input_rows=table.new_scan().plan().row_count,
                     written_rows=sum(len(r["id"]) for r in runs))
