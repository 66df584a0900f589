"""Seeded YCSB workload-A updates of the `usertable` (`ycsb-usertable`).

numpy only, vectorised, by YCSB's `CoreWorkload` with its defaults:

* a record number r names the key "user" + the decimal digits of
  `fnvhash64(r)` (`insertorder=hashed`, `zeropadding=1`): 18 to 23 bytes,
  23 in almost every row.  The digits come from one array per digit
  place, never from a per-row `%` in Python;
* an update picks its record by `ScrambledZipfianGenerator(0,
  recordcount - 1)`: a zipfian rank at theta 0.99 over 10^10 items
  (YCSB's precomputed zeta), FNV-hashed, then taken mod `recordcount`;
* it writes one field of ten, chosen uniformly (`writeallfields=false`),
  with 100 printable ASCII bytes (`RandomByteIterator` draws from ' '
  upwards); the other nine are null, as a partial-update row has them.

The record numbers (so the keys, the buckets' row counts and every
padded program size) come from `data.key_seed`; the field chosen and
its bytes from `--seed`.  A commit is a dict: `keys` (offsets int32[n+1],
bytes uint8), `field` int8[n], `values` uint8[n, length].  `to_arrow`
makes the table the writer takes, every column built from its buffers.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

_FNV_OFFSET = np.uint64(0xCBF29CE484222325)
_FNV_PRIME = np.uint64(1099511628211)

# ZipfianGenerator(0, ITEM_COUNT, 0.99, ZETAN): the item count and the
# zeta constant ScrambledZipfianGenerator uses for theta 0.99
ZIPF_ITEMS = 10_000_000_000
ZIPF_THETA = 0.99
ZIPF_ZETAN = 26.46902820178302


def fnvhash64(values: np.ndarray) -> np.ndarray:
    """YCSB `Utils.fnvhash64` of non-negative longs: FNV-1 over the 8
    low-to-high bytes, then `Math.abs` (int64)."""
    v = values.astype(np.uint64)
    h = np.full(len(v), _FNV_OFFSET, dtype=np.uint64)
    with np.errstate(over="ignore"):
        for k in range(8):
            h ^= (v >> np.uint64(8 * k)) & np.uint64(0xFF)
            h *= _FNV_PRIME
    return np.abs(h.view(np.int64))


def zipf_ranks(u: np.ndarray) -> np.ndarray:
    """`ZipfianGenerator.nextLong(ITEM_COUNT)` for uniform draws `u`."""
    theta, n = ZIPF_THETA, ZIPF_ITEMS
    zeta2 = 1.0 + 0.5 ** theta
    alpha = 1.0 / (1.0 - theta)
    eta = (1.0 - (2.0 / n) ** (1.0 - theta)) / (1.0 - zeta2 / ZIPF_ZETAN)
    uz = u * ZIPF_ZETAN
    ranks = (n * np.power(eta * u - eta + 1.0, alpha)).astype(np.int64)
    ranks[uz < 1.0 + 0.5 ** theta] = 1
    ranks[uz < 1.0] = 0
    return ranks


def records(u: np.ndarray, recordcount: int) -> np.ndarray:
    """`ScrambledZipfianGenerator.nextValue()` over [0, recordcount)."""
    return fnvhash64(zipf_ranks(u)) % recordcount


def key_bytes(recs: np.ndarray):
    """(offsets int32[n+1], bytes uint8) of "user" + decimal digits of
    `fnvhash64(record)`: the digits right-aligned in a 23-byte row a
    place at a time, then the used bytes of every row in row order."""
    h = fnvhash64(recs).astype(np.uint64)
    n = len(h)
    width = 4 + 19
    mat = np.empty((n, width), dtype=np.uint8)
    digits = np.ones(n, dtype=np.int64)
    q = h.copy()
    for place in range(19):
        mat[:, width - 1 - place] = (q % np.uint64(10)).astype(np.uint8) \
            + ord("0")
        q //= np.uint64(10)
        digits += (place > 0) & (h >= np.uint64(10 ** place))
    col = np.arange(width)
    start = width - digits - 4
    for i, c in enumerate(b"user"):
        mat[np.arange(n), start + i] = c
    used = col[None, :] >= start[:, None]
    offsets = np.zeros(n + 1, dtype=np.int32)
    np.cumsum(digits + 4, out=offsets[1:])
    return offsets, mat[used]


def gen_commits(seed: int, data: dict, workload: dict):
    """`pool_commits` commits of `commit_updates` workload-A updates."""
    n = data["commit_updates"]
    key_streams = np.random.SeedSequence(data["key_seed"]).spawn(
        data["pool_commits"])
    value_streams = np.random.SeedSequence(seed).spawn(data["pool_commits"])
    length = workload["fieldlength"]

    def one(ks, vs):
        recs = records(np.random.default_rng(ks).random(n),
                       data["recordcount"])
        g = np.random.default_rng(vs)
        values = np.frombuffer(g.bytes(n * length), dtype=np.uint8)
        # RandomByteIterator's bytes: printable ASCII from ' ' up
        values = (values % 95 + 32).reshape(n, length)
        return {"records": recs, "keys": key_bytes(recs),
                "field": g.integers(0, workload["fieldcount"], n)
                .astype(np.int8),
                "values": values}

    with ThreadPoolExecutor(max_workers=len(key_streams)) as pool:
        return list(pool.map(one, key_streams, value_streams))


def field_names(workload: dict):
    return [f"field{i}" for i in range(workload["fieldcount"])]


def to_arrow(commit: dict, workload: dict):
    """The commit as the writer takes it: the key, then each field a
    string column holding its rows' 100 bytes and null elsewhere."""
    import pyarrow as pa

    offsets, chars = commit["keys"]
    n = len(offsets) - 1
    cols = {"YCSB_KEY": pa.Array.from_buffers(
        pa.string(), n, [None, pa.py_buffer(offsets), pa.py_buffer(chars)])}
    length = commit["values"].shape[1]
    for f, name in enumerate(field_names(workload)):
        mine = commit["field"] == f
        offs = np.zeros(n + 1, dtype=np.int32)
        np.cumsum(mine * length, out=offs[1:])
        data = np.ascontiguousarray(commit["values"][mine])
        valid = np.packbits(mine, bitorder="little")
        cols[name] = pa.Array.from_buffers(
            pa.string(), n, [pa.py_buffer(valid), pa.py_buffer(offs),
                             pa.py_buffer(data)],
            null_count=int(n - mine.sum()))
    return pa.table(cols)


def create_table(path: str, table_cfg: dict):
    from paimon_tpu.schema import Schema
    from paimon_tpu.table import FileStoreTable
    from paimon_tpu.types import parse_data_type

    builder = Schema.builder()
    for column, sql in table_cfg["columns"]:
        builder = builder.column(column, parse_data_type(sql))
    schema = builder.primary_key(*table_cfg["primary_key"]).options(
        {"bucket": str(table_cfg["buckets"]),
         **table_cfg["options"]}).build()
    return FileStoreTable.create(path, schema)
