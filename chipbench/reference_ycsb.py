"""The plain reference of the YCSB usertable after workload-A commits.

numpy and pyarrow only, nothing of `paimon_tpu`: keys compared by their
full bytes, no prefix lanes.  Under partial-update each field of a key
is the last non-null value written to it in commit order, then arrival
order within a commit; a key no update touched is not in the table (no
load phase is written).
"""

from __future__ import annotations

import numpy as np

from chipbench.reference import Mismatch


def fold(commits, committed, fields):
    """The table after the commits `committed` (indices into `commits`,
    in commit order), sorted by key bytes.  A commit committed again
    overrides every value it sets, so each is folded once, at its last
    place."""
    import pyarrow as pa
    import pyarrow.compute as pc

    last = {j: pos for pos, j in enumerate(committed)}
    folded = sorted(last, key=last.get)
    keys = pa.chunked_array([_keys(commits[j]) for j in folded])
    field = np.concatenate([commits[j]["field"] for j in folded])
    starts = np.cumsum([0] + [len(commits[j]["field"]) for j in folded])
    codes = keys.dictionary_encode().combine_chunks()
    distinct = codes.dictionary
    codes = codes.indices.to_numpy().astype(np.int64)
    rows = np.arange(len(field))
    order = pc.sort_indices(distinct.cast(pa.binary())).to_numpy()
    out = {"YCSB_KEY": distinct.take(pa.array(order))}
    for f, name in enumerate(fields):
        mine = field == f
        writer = np.full(len(distinct), -1, dtype=np.int64)
        np.maximum.at(writer, codes[mine], rows[mine])
        out[name] = _values_at(commits, folded, starts, writer[order])
    return pa.table(out)


def _keys(commit):
    import pyarrow as pa
    offsets, chars = commit["keys"]
    return pa.Array.from_buffers(
        pa.string(), len(offsets) - 1,
        [None, pa.py_buffer(offsets), pa.py_buffer(chars)])


def _values_at(commits, folded, starts, rows):
    """A string column: the value of global row `rows[i]`, null where it
    is -1."""
    import pyarrow as pa
    length = commits[folded[0]]["values"].shape[1]
    have = rows >= 0
    data = np.empty((int(have.sum()), length), dtype=np.uint8)
    picked = rows[have]
    which = np.searchsorted(starts, picked, side="right") - 1
    out_at = np.arange(len(picked))
    for k, j in enumerate(folded):
        sel = which == k
        data[out_at[sel]] = commits[j]["values"][picked[sel] - starts[k]]
    offsets = np.zeros(len(rows) + 1, dtype=np.int32)
    np.cumsum(have * length, out=offsets[1:])
    return pa.Array.from_buffers(
        pa.string(), len(rows),
        [pa.py_buffer(np.packbits(have, bitorder="little")),
         pa.py_buffer(offsets), pa.py_buffer(data)],
        null_count=int(len(rows) - have.sum()))


def check(got, want, what: str):
    """`got` (the scan, in any row order) equals `want` row for row once
    sorted by key bytes, in every column."""
    import pyarrow as pa
    import pyarrow.compute as pc
    if got.num_rows != want.num_rows:
        raise Mismatch(f"{what}: {got.num_rows} rows, reference has "
                       f"{want.num_rows}")
    order = pc.sort_indices(got.column("YCSB_KEY").cast(pa.binary()))
    got = got.take(order)
    for name in want.column_names:
        have, ref = got.column(name).combine_chunks(), \
            want.column(name).combine_chunks()
        if have.equals(ref):
            continue
        same = pc.fill_null(pc.equal(have, ref), False).to_numpy(
            zero_copy_only=False)
        same |= (pc.is_null(have).to_numpy(zero_copy_only=False)
                 & pc.is_null(ref).to_numpy(zero_copy_only=False))
        bad = np.flatnonzero(~same)
        i = int(bad[0]) if len(bad) else 0
        raise Mismatch(f"{what}: {len(bad)} rows differ in {name}; first "
                       f"at key {want.column('YCSB_KEY')[i]}: got "
                       f"{str(have[i])[:40]!r}, reference "
                       f"{str(ref[i])[:40]!r}")
