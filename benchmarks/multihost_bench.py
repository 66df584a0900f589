"""Multi-host write-plane benchmark: 1-process vs 2-process ingest of
the SAME fixed-seed batch stream on one machine, row identity asserted
against the single-process oracle.

The 2-process leg is a REAL gloo mesh (the test_multihost_real
recipe): both workers run the identical SPMD program, each keeps the
rows hashing to its owned buckets (multihost.write.routing=spmd, so
no per-batch exchange collective inflates the measurement), flushes
through its own per-bucket actor pipeline, and commits through CAS
arbitration.  Wall time is measured between two mesh barriers, so
process bring-up is excluded.

Usage:
    python -m benchmarks.multihost_bench [rows]
Prints ONE JSON line per measurement (micro.py style) and a final
summary dict on stdout when run under measure().
"""

import json
import os
import socket
import subprocess
import sys
import tempfile
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402
import pyarrow as pa  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

BUCKETS = 8


def _schema():
    from paimon_tpu.schema import Schema
    from paimon_tpu.types import BigIntType, IntType
    return (Schema.builder()
            .column("id", BigIntType(False))
            .column("v", IntType())
            .primary_key("id")
            .options({"bucket": str(BUCKETS), "write-only": "true"})
            .build())


def _data(rows: int, seed: int = 13) -> pa.Table:
    rng = np.random.default_rng(seed)
    return pa.table({
        "id": pa.array(rng.permutation(rows), pa.int64()),
        "v": pa.array(rng.integers(0, 1 << 30, rows).astype(np.int32),
                      pa.int32()),
    })


def _ingest_single(tmp: str, rows: int, reps: int = 2) -> float:
    """Single-process oracle ingest; returns best-of wall seconds
    (the last rep's table at <tmp>/oracle is the comparison oracle)."""
    from paimon_tpu.table import FileStoreTable
    data = _data(rows)
    best = float("inf")
    for r in range(reps):
        path = os.path.join(tmp, "oracle" if r == reps - 1
                            else f"oracle-warm{r}")
        t = FileStoreTable.create(path, _schema())
        t0 = time.perf_counter()
        wb = t.new_batch_write_builder()
        with wb.new_write() as w:
            w.write_arrow(data)
            wb.new_commit().commit(w.prepare_commit())
        best = min(best, time.perf_counter() - t0)
    return best


_WORKER = r'''
import os, sys, time
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax

pid = int(sys.argv[1]); port = sys.argv[2]; table_path = sys.argv[3]
sys.path.insert(0, sys.argv[4]); rows = int(sys.argv[5])

from paimon_tpu.parallel import multihost as MH
MH.initialize(f"127.0.0.1:{port}", 2, pid)

from benchmarks.multihost_bench import BUCKETS, _data, _schema
from paimon_tpu.table import FileStoreTable

data = _data(rows)                  # identical global batch (SPMD)

# best-of-2 like the single-process leg: rep 0 pays the collective
# jit warmup (first barrier/allgather compile), rep 1 is the warmed
# number; the LAST rep's table (<path>) is the one the parent audits
dt = float("inf")
for rep, path in enumerate((table_path + "-warm", table_path)):
    if pid == 0:
        FileStoreTable.create(path, _schema())
    MH.barrier(f"bench-table-{rep}")
    t = FileStoreTable.load(
        path, dynamic_options={"multihost.write.routing": "spmd"})
    plane = t.new_distributed_write()
    MH.barrier(f"bench-start-{rep}")
    t0 = time.perf_counter()
    plane.write_arrow(data)
    plane.commit()
    MH.barrier(f"bench-end-{rep}")
    dt = min(dt, time.perf_counter() - t0)
    plane.close()
if pid == 0:
    import json
    from paimon_tpu.metrics import global_registry
    snap = global_registry().snapshot()
    print(json.dumps({
        "dt": dt,
        "metrics_snapshot": {k: v for k, v in snap.items()
                             if k.startswith("multihost")},
    }), flush=True)
print(f"proc {pid}: BENCH-MH-OK", flush=True)
'''


def _ingest_two_process(tmp: str, rows: int, timeout: float) -> dict:
    """2-process mesh ingest of the same batch; returns worker 0's
    summary ({dt, metrics_snapshot}) with wall seconds measured
    between the start/end barriers (bring-up excluded)."""
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    worker = os.path.join(tmp, "mh_bench_worker.py")
    with open(worker, "w") as f:
        f.write(_WORKER)
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    table_path = os.path.join(tmp, "dist")
    procs = [subprocess.Popen(
        [sys.executable, worker, str(pid), str(port), table_path,
         REPO, str(rows)],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=env) for pid in (0, 1)]
    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        outs.append(out)
    for pid, (p, out) in enumerate(zip(procs, outs)):
        if p.returncode != 0:
            raise RuntimeError(f"bench worker {pid} rc={p.returncode}:"
                               f"\n{out[-3000:]}")
    for line in outs[0].splitlines():
        line = line.strip()
        if line.startswith("{"):
            return json.loads(line)
    raise RuntimeError(f"no timing line from worker 0:\n{outs[0][-2000:]}")


def _emit(name: str, rows: int, seconds: float, **extra):
    out = {"benchmark": name, "value": round(rows / seconds, 1),
           "unit": "rows/s", "rows": rows,
           "best_seconds": round(seconds, 6)}
    out.update(extra)
    print(json.dumps(out), flush=True)


def measure(rows: int = 400_000, timeout: float = 300.0) -> dict:
    """The multihost_write bench block: 1-proc vs 2-proc ingest of the
    same fixed-seed batch, final table asserted IDENTICAL to the
    single-process oracle.  Returns the summary dict bench.py banks."""
    from paimon_tpu.table import FileStoreTable
    with tempfile.TemporaryDirectory() as tmp:
        dt1 = _ingest_single(tmp, rows)
        worker = _ingest_two_process(tmp, rows, timeout)
        dt2 = float(worker["dt"])
        oracle = FileStoreTable.load(
            os.path.join(tmp, "oracle")).to_arrow().sort_by("id")
        dist = FileStoreTable.load(
            os.path.join(tmp, "dist")).to_arrow().sort_by("id")
        identical = oracle.equals(dist)
        fsck_ok = FileStoreTable.load(os.path.join(tmp, "dist")).fsck().ok
    _emit("multihost_write_1proc", rows, dt1)
    _emit("multihost_write_2proc", rows, dt2,
          identical=identical, fsck_ok=fsck_ok,
          vs_1proc=round(dt1 / dt2, 3))
    assert identical, "2-process ingest diverged from the oracle"
    assert fsck_ok, "2-process table not fsck-clean"
    return {"rows": rows, "dt_1proc": dt1, "dt_2proc": dt2,
            "identical": identical, "fsck_ok": fsck_ok,
            "metrics_snapshot": worker.get("metrics_snapshot")}


if __name__ == "__main__":
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 400_000
    print(json.dumps(measure(n)), flush=True)
