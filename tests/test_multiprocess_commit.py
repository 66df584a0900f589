"""Cross-process commit contention: real OS processes race the
rename-CAS snapshot publish.

reference intent: FileStoreCommitImpl's optimistic retry under
concurrent committers (tryCommit loop :756) — here exercised by
actual concurrent processes, not injected races.
"""

import os
import subprocess
import sys

import pytest

_WORKER = r"""
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
from paimon_tpu.table import FileStoreTable

path, worker_id, n_commits = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])
t = FileStoreTable.load(path)
for i in range(n_commits):
    wb = t.new_batch_write_builder()
    w = wb.new_write()
    w.write_dicts([{"id": worker_id * 1000 + i,
                    "v": float(worker_id)}])
    sid = wb.new_commit().commit(w.prepare_commit())
    assert sid is not None
    w.close()
print("worker", worker_id, "done")
"""


@pytest.mark.parametrize("workers,commits", [(4, 5)])
def test_concurrent_processes_commit(tmp_path, workers, commits):
    from paimon_tpu.schema import Schema
    from paimon_tpu.table import FileStoreTable
    from paimon_tpu.types import BigIntType, DoubleType

    schema = (Schema.builder()
              .column("id", BigIntType(False))
              .column("v", DoubleType())
              .primary_key("id")
              .options({"bucket": "2", "write-only": "true"})
              .build())
    path = str(tmp_path / "t")
    FileStoreTable.create(path, schema)

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.dirname(os.path.dirname(
                   os.path.abspath(__file__))))
    procs = [subprocess.Popen(
        [sys.executable, "-c", _WORKER, path, str(w), str(commits)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE)
        for w in range(workers)]
    for p in procs:
        out, err = p.communicate(timeout=300)
        assert p.returncode == 0, err.decode()[-2000:]

    t = FileStoreTable.load(path)
    # every commit won a distinct snapshot; no write was lost
    assert t.latest_snapshot().id == workers * commits
    rows = t.to_arrow().to_pylist()
    assert len(rows) == workers * commits
    expected = {w * 1000 + i for w in range(workers)
                for i in range(commits)}
    assert {r["id"] for r in rows} == expected
