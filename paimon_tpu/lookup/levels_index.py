"""The upper levels of one bucket, indexed for the lookup changelog
producer.

reference: mergetree/LookupLevels.java:56 (lookup:137) and
LookupChangelogMergeFunctionWrapper.java:54: with
`changelog-producer=lookup` every commit compacts its new level-0 files
and looks up, for each key they touch, the value the levels above hold,
so that the changelog says +I, -U/+U or -D.

Each sorted run above level 0 (one a level) is held two ways, for as
long as the index lives (the bucket writer's life, across commits):

- its KV rows on the host, in key order, as decoded or as the
  compaction that wrote the run had them in memory; a file's rows are a
  slice of its run's (`table_of`), so a compaction that rewrites an
  upper run decodes none of its files;
- its normalized key lanes on the device (`ops/lookup_probe.py`), where
  one program probes a whole batch of keys against the run.

`sync` brings the index to a bucket's file set, decoding only runs it
does not hold (its first use builds it whole); `apply` follows one
compaction: the runs it consumed leave, its output run comes in from
memory.  `gather` answers a batch of probes with the rows of the runs
that hold the keys.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np
import pyarrow as pa

from paimon_tpu.manifest import DataFileMeta
from paimon_tpu.metrics import (
    LOOKUP_GATHER_MS, LOOKUP_INDEX_DEVICE_BYTES, LOOKUP_INDEX_MS,
    LOOKUP_INDEX_ROWS, LOOKUP_LEVEL_ROWS_DECODED, LOOKUP_PROBE_HITS,
    LOOKUP_PROBE_KEYS, LOOKUP_PROBE_MS, global_registry,
)
from paimon_tpu.obs.trace import span
from paimon_tpu.ops import lookup_probe
from paimon_tpu.ops.normkey import NormalizedKeyEncoder

__all__ = ["LevelsIndex"]


def _metrics():
    return global_registry().lookup_metrics()


@dataclass
class _Run:
    level: int
    files: List[DataFileMeta]           # key order
    table: pa.Table                     # the run's KV rows, key order
    device: object                      # uint32[L, capacity] on the device
    truncated: Optional[np.ndarray]     # per row, for cut-key encoders
    offsets: Dict[str, Tuple[int, int]] = field(default_factory=dict)

    @property
    def rows(self) -> int:
        return self.table.num_rows

    @property
    def names(self) -> List[str]:
        return [f.file_name for f in self.files]


class LevelsIndex:
    """One bucket's runs above level 0: host rows, device key lanes."""

    def __init__(self, key_encoder: NormalizedKeyEncoder,
                 key_cols: Sequence[str]):
        self.key_encoder = key_encoder
        self.key_cols = list(key_cols)
        self._runs: Dict[int, _Run] = {}
        self._schema: Optional[pa.Schema] = None

    # -- what it holds --------------------------------------------------------

    @property
    def levels(self) -> Dict[int, List[str]]:
        """{level: the run's file names, key order}."""
        return {lvl: run.names for lvl, run in sorted(self._runs.items())}

    def table_of(self, f: DataFileMeta) -> Optional[pa.Table]:
        """The rows of an upper-level file the index holds, or None."""
        run = self._runs.get(f.level)
        if run is None or f.file_name not in run.offsets:
            return None
        start, rows = run.offsets[f.file_name]
        return run.table.slice(start, rows)

    def run_lanes(self, level: int) -> np.ndarray:
        """The device lanes of one run, back on the host as
        `uint32[rows, L]` (tests compare them with a rebuilt index)."""
        run = self._runs[level]
        return np.asarray(run.device)[:, :run.rows].T

    # -- keeping it --------------------------------------------------------------

    def sync(self, files: Sequence[DataFileMeta],
             decode: Callable[[List[DataFileMeta]], List[pa.Table]]):
        """Hold exactly the runs of `files` above level 0: a run the
        index holds file for file stays, any other leaves, and each run
        it lacks is decoded (`decode`: the files' KV tables, in order)
        and put on the device."""
        wanted: Dict[int, List[DataFileMeta]] = {}
        for f in files:
            if f.level > 0:
                wanted.setdefault(f.level, []).append(f)
        for lvl in wanted:
            wanted[lvl].sort(key=lambda f: f.min_key)
        for lvl in list(self._runs):
            if [f.file_name for f in wanted.get(lvl, [])] \
                    != self._runs[lvl].names:
                del self._runs[lvl]
        missing = [(lvl, fs) for lvl, fs in sorted(wanted.items())
                   if lvl not in self._runs]
        if not missing:
            return
        flat = [f for _, fs in missing for f in fs]
        tables = decode(flat)
        decoded = sum(t.num_rows for t in tables)
        _metrics().counter(LOOKUP_LEVEL_ROWS_DECODED).inc(decoded)
        at = 0
        for lvl, fs in missing:
            own = tables[at:at + len(fs)]
            at += len(fs)
            self._schema = own[0].schema
            self._runs[lvl] = self._make_run(
                lvl, fs, pa.concat_tables(own, promote_options="none")
                if len(own) > 1 else own[0])

    def apply(self, before: Sequence[DataFileMeta],
              after: Sequence[DataFileMeta],
              output: Optional[pa.Table]):
        """Follow one compaction of this bucket: the runs that held
        `before` files leave, and `after` (one run at its level) comes
        in — a moved run as it was, a rewritten one from `output`, the
        rows the compaction wrote, in file order.  A run it cannot take
        from memory is left out, and the next `sync` decodes it."""
        moved = {f.file_name: f for f in before}
        old = {lvl: run for lvl, run in self._runs.items()
               if any(n in moved for n in run.names)}
        for lvl in old:
            del self._runs[lvl]
        upper = [f for f in after if f.level > 0]
        if not upper:
            return
        level = upper[0].level
        if all(f.file_name in moved for f in upper):
            # a metadata-only promotion: the same rows at another level
            src = old.get(moved[upper[0].file_name].level)
            if src is not None and src.names == [f.file_name
                                                 for f in upper]:
                src.level, src.files = level, list(upper)
                self._runs[level] = src
            return
        if output is None or sum(f.row_count for f in upper) \
                != output.num_rows or (self._schema is not None and
                                       not output.schema.equals(
                                           self._schema)):
            return
        self._runs[level] = self._make_run(level, list(upper), output)

    def _make_run(self, level: int, files: List[DataFileMeta],
                  table: pa.Table) -> _Run:
        nbytes = lookup_probe.capacity(table.num_rows) \
            * self.key_encoder.num_lanes * 4
        with span("lookup.index", cat="lookup", group="lookup",
                  metric=LOOKUP_INDEX_MS, level=level,
                  rows=table.num_rows, bytes=nbytes):
            lanes, truncated = self.key_encoder.encode_table(
                table, self.key_cols)
            device = lookup_probe.device_lanes(lanes)
        m = _metrics()
        m.counter(LOOKUP_INDEX_ROWS).inc(table.num_rows)
        m.counter(LOOKUP_INDEX_DEVICE_BYTES).inc(nbytes)
        run = _Run(level, files, table, device,
                   None if self.key_encoder.fixed_width else truncated)
        at = 0
        for f in files:
            run.offsets[f.file_name] = (at, f.row_count)
            at += f.row_count
        return run

    # -- answering probes ----------------------------------------------------------

    def _newest_first(self) -> List[_Run]:
        return [self._runs[lvl] for lvl in sorted(self._runs)]

    def probe(self, probes: pa.Table) -> List[np.ndarray]:
        """For each run, newest first: int64[P] rows of the run that
        hold the probes' keys, -1 where it holds none."""
        n = probes.num_rows
        runs = self._newest_first()
        if not n or not runs:
            return [np.full(n, -1, np.int64) for _ in runs]
        lanes, truncated = self.key_encoder.encode_table(probes,
                                                         self.key_cols)
        lanes = np.asarray(lanes)
        cut = not self.key_encoder.fixed_width
        key_bytes = lanes.shape[1] * 4
        found = []
        for run in runs:
            with span("lookup.probe", cat="lookup", group="lookup",
                      metric=LOOKUP_PROBE_MS, probes=n,
                      index_rows=run.rows, key_bytes=key_bytes,
                      h2d_bytes=lookup_probe.capacity(n) * key_bytes,
                      d2h_bytes=n * (8 if cut else 4)):
                rows, ends = lookup_probe.probe(run.device, run.rows,
                                                lanes, upper=cut)
            rows = rows.astype(np.int64)
            if cut:
                rows = self._confirm(run, probes, lanes, truncated, rows,
                                     ends.astype(np.int64))
            found.append(rows)
        hits = np.zeros(n, dtype=bool)
        for rows in found:
            hits |= rows >= 0
        m = _metrics()
        m.counter(LOOKUP_PROBE_KEYS).inc(n)
        m.counter(LOOKUP_PROBE_HITS).inc(int(hits.sum()))
        return found

    def _confirm(self, run: _Run, probes: pa.Table, lanes: np.ndarray,
                 truncated: np.ndarray, rows: np.ndarray,
                 ends: np.ndarray) -> np.ndarray:
        """Probes whose lanes match where a key is cut to its prefix.
        Among rows of equal lanes a key the lanes determine sorts first
        (it is a prefix of the others), so an uncut probe hits that row
        alone; a cut probe is compared with each cut row of the range
        by its full bytes: the candidates and the probes are put in
        exact order by `tiebreak_cut_keys`, and a probe hits the
        candidate its full key equals."""
        from paimon_tpu.ops.merge import tiebreak_cut_keys

        out = rows.copy()
        lit = rows >= 0
        plain = lit & ~truncated
        out[plain & run.truncated[np.maximum(rows, 0)]] = -1
        asks = np.flatnonzero(lit & truncated)
        out[asks] = -1
        if not len(asks):
            return out
        counts = ends[asks] - rows[asks]
        starts = np.repeat(np.cumsum(counts) - counts, counts)
        cand = np.repeat(rows[asks], counts) + \
            (np.arange(int(counts.sum())) - starts)
        cand = np.unique(cand[run.truncated[cand]])
        if not len(cand):
            return out
        keys = pa.concat_tables(
            [run.table.select(self.key_cols).take(pa.array(cand)),
             probes.select(self.key_cols).take(pa.array(asks))],
            promote_options="none")
        both, cut = self.key_encoder.encode_table(keys, self.key_cols)
        both = np.asarray(both)
        perm = np.lexsort(both.T[::-1])
        order, same = tiebreak_cut_keys(
            keys, self.key_cols, self.key_encoder, both, cut, perm,
            np.zeros(len(both), dtype=np.int64))
        segment = np.concatenate([[0], np.cumsum(~same)])
        is_cand = order < len(cand)
        hit_of = np.full(int(segment[-1]) + 1, -1, np.int64)
        hit_of[segment[is_cand]] = cand[order[is_cand]]
        out[asks[order[~is_cand] - len(cand)]] = \
            hit_of[segment[~is_cand]]
        return out

    def gather(self, probes: pa.Table) -> List[pa.Table]:
        """For any probes: each run's rows that hold one of their keys,
        once each, in key order; the runs oldest first (a merge's
        order), runs that hold none left out."""
        found = self.probe(probes)
        runs = self._newest_first()
        with span("lookup.gather", cat="lookup", group="lookup",
                  metric=LOOKUP_GATHER_MS,
                  rows=int(sum((r >= 0).sum() for r in found))):
            out = []
            for run, rows in zip(reversed(runs), reversed(found)):
                hit = np.unique(rows[rows >= 0])
                if len(hit):
                    out.append(run.table.take(pa.array(hit)))
            return out
