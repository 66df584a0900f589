"""Plain reference of `changelog-producer=lookup`: a dict of the table's
rows, applied one commit at a time, in plain Python.

Per commit, the rows of each key fold in arrival order under the merge
engine: deduplicate keeps the last row (a delete removes the key),
partial-update sets each field to the last non-null value written,
first-row keeps the first row a key ever had.  The commit's changelog
then says, for each key it touched: +I(new) where the key was absent
before, -U(old) then +U(new) where it was present (also when the value
did not change: `changelog-producer.row-deduplicate` defaults to false),
-D(old) where a present key was deleted, and nothing where an absent key
was deleted.  Upstream: LookupChangelogMergeFunctionWrapper.java:54.
First-row's changelog is +I alone, for the keys absent before; a key
that was there gives nothing (FirstRowMergeFunctionWrapper).
"""

from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

INSERT, UPDATE_BEFORE, UPDATE_AFTER, DELETE = 0, 1, 2, 3


class LookupChangelogReference:
    def __init__(self, key_fields: Sequence[str],
                 value_fields: Sequence[str], engine: str):
        if engine not in ("deduplicate", "partial-update", "first-row"):
            raise ValueError(f"no reference for {engine!r}")
        self.key_fields = list(key_fields)
        self.value_fields = list(value_fields)
        self.engine = engine
        self.state: Dict[Tuple, Dict] = {}

    def key(self, row: Dict) -> Tuple:
        return tuple(row[k] for k in self.key_fields)

    def _row(self, key: Tuple, values: Dict) -> Dict:
        out = dict(zip(self.key_fields, key))
        out.update({f: values.get(f) for f in self.value_fields})
        return out

    def commit(self, rows: Sequence[Dict],
               kinds: Sequence[int]) -> List[Tuple[int, Dict]]:
        """Apply one commit; returns its changelog as (kind, row), keys
        in the order of their first row in the commit."""
        touched: Dict[Tuple, object] = {}
        for row, kind in zip(rows, kinds):
            k = self.key(row)
            current = touched.get(k, self.state.get(k))
            if self.engine == "first-row":
                if current is None:
                    touched[k] = {f: row.get(f) for f in self.value_fields}
                continue
            if kind == DELETE:
                touched[k] = None
            elif self.engine == "deduplicate" or current is None:
                touched[k] = {f: row.get(f) for f in self.value_fields}
            else:
                merged = dict(current)
                for f in self.value_fields:
                    if row.get(f) is not None:
                        merged[f] = row[f]
                touched[k] = merged
        changelog = []
        for k, new in touched.items():
            old = self.state.get(k)
            if new is None:
                if old is not None:
                    changelog.append((DELETE, self._row(k, old)))
                    del self.state[k]
                continue
            if old is None:
                changelog.append((INSERT, self._row(k, new)))
            elif self.engine == "first-row":
                continue
            else:
                changelog.append((UPDATE_BEFORE, self._row(k, old)))
                changelog.append((UPDATE_AFTER, self._row(k, new)))
            self.state[k] = new
        return changelog

    def rows(self) -> List[Dict]:
        return [self._row(k, v) for k, v in sorted(self.state.items())]
