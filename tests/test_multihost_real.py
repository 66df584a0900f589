"""REAL multi-process multi-host execution: two OS processes bring up
jax's distributed runtime (Gloo-backed CPU collectives), form one
global 8-device mesh (4 local devices each), write disjoint partitions
of the SAME table (per-process commit users, CAS-serialized commits),
take deterministic split ownership, and reduce a globally-sharded
array with a cross-process collective.

This exercises the actual multi-host contract of
`parallel/multihost.py` — not the single-process degradation the other
multihost tests cover.  reference: SURVEY §5 "distributed
communication backend" (engine RPC/NCCL) -> jax distributed runtime +
XLA DCN collectives.

Root cause of the long-standing failure (triaged in the
tail-tolerance PR): jax 0.4.x ships the CPU backend with
cross-process collectives DISABLED — the distributed runtime, table
writes, CAS commits and split ownership all worked, but the final
jitted cross-process reduction died with "Multiprocess computations
aren't implemented on the CPU backend".  Fixed by opting into the
Gloo implementation (`jax_cpu_collectives_implementation=gloo`)
inside `multihost.initialize()` before the backend comes up.  For
jaxlib builds genuinely lacking Gloo the same error (or the flag's
absence) is detected in the worker output and the test SKIPS with the
recorded reason instead of failing tier-1.
"""

import os
import socket
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# capability marker: jaxlib builds without Gloo cross-process CPU
# collectives fail with exactly this (see module docstring) — an
# environment limit, not a paimon_tpu bug
_NO_CPU_COLLECTIVES = "Multiprocess computations aren't implemented"

WORKER = r'''
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax

pid = int(sys.argv[1]); port = sys.argv[2]; table_path = sys.argv[3]
sys.path.insert(0, sys.argv[4])

from paimon_tpu.parallel import multihost as MH

idx, count = MH.initialize(f"127.0.0.1:{port}", 2, pid)
assert (idx, count) == (pid, 2)
assert jax.local_device_count() == 4 and jax.device_count() == 8

from paimon_tpu import Schema
from paimon_tpu.table import FileStoreTable
from paimon_tpu.types import BigIntType, IntType, VarCharType

ROWS = 128
schema = (Schema.builder()
          .column("part", VarCharType(nullable=False))
          .column("id", BigIntType(False))
          .column("v", IntType())
          .partition_keys("part")
          .primary_key("id", "part")
          .options({"bucket": "1"}).build())
if pid == 0:
    t = FileStoreTable.create(table_path, schema)
else:
    import time
    for _ in range(100):
        try:
            t = FileStoreTable.load(table_path)
            break
        except Exception:
            time.sleep(0.1)
    else:
        raise RuntimeError("table never appeared")

# each process commits its own partition; the snapshot CAS serializes
user = MH.distributed_write_commit_user()
assert user.endswith(f"p{pid}")
wb = t.new_batch_write_builder()
wb.commit_user = user
w = wb.new_write()
w.write_dicts([{"part": f"h{pid}", "id": i, "v": pid}
               for i in range(ROWS)])
wb.new_commit().commit(w.prepare_commit())
w.close()

# barrier: wait until BOTH commits are visible, then plan the same scan
import time
for _ in range(200):
    t = FileStoreTable.load(table_path)
    if (t.snapshot_manager.latest_snapshot() is not None
            and t.to_arrow().num_rows == 2 * ROWS):
        break
    time.sleep(0.1)
else:
    raise RuntimeError("second commit never became visible")

splits = sorted(t.new_read_builder().new_scan().plan().splits,
                key=lambda s: s.partition)
mine = MH.assign_splits(splits)
assert len(mine) == 1, "round-robin ownership must be disjoint"

import pyarrow as pa
read = t.new_read_builder().new_read()
local = pa.concat_tables([read.read_split(s) for s in mine],
                         promote_options="none")
assert local.num_rows == ROWS

# every process feeds ITS rows into one globally-sharded array; the
# jitted reductions run cross-process collectives over Gloo
import numpy as np
import jax.numpy as jnp
mesh = MH.global_mesh(("b",))
g = MH.process_local_batch(mesh, {
    "v": np.asarray(local.column("v").combine_chunks(), dtype=np.int32),
}, axis="b")
total = int(jax.jit(jnp.sum)(g["v"]))
n = int(np.prod(g["v"].shape))
assert n == 2 * ROWS, n
assert total == ROWS * 1, total        # pid-0 rows are 0, pid-1 rows are 1
print(f"proc {pid}: MULTIHOST-OK n={n} sum={total}", flush=True)
'''


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_two_process_multihost(tmp_path):
    port = _free_port()
    table_path = str(tmp_path / "t")
    worker_py = tmp_path / "worker.py"
    worker_py.write_text(WORKER)
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)       # worker pins its own device count
    procs = [subprocess.Popen(
        [sys.executable, str(worker_py), str(pid), str(port),
         table_path, REPO],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=env) for pid in (0, 1)]
    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=420)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        outs.append(out)
    if any(_NO_CPU_COLLECTIVES in out for out in outs):
        pytest.skip(
            "jaxlib CPU backend lacks Gloo cross-process collectives "
            "(jax_cpu_collectives_implementation=gloo unavailable); "
            "multi-host CPU emulation cannot run here")
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"proc {pid} failed:\n{out[-4000:]}"
        assert f"proc {pid}: MULTIHOST-OK n=256 sum=128" in out, out[-2000:]
