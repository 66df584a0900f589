"""The plain reference of `changelog-producer=lookup` over
`mor50m-dedup`'s table, and the comparison that decides `correct`.

The table is a dense state over the key space (present, v1, v2, v3 per
key), folded one commit at a time in numpy: within a commit the last
row of each key wins (deduplicate), and the commit's changelog holds,
for each key it touched, +I(new) where the key was absent, -U(old) then
+U(new) where it was present, also when the values are equal
(`changelog-producer.row-deduplicate` is false).  Imports nothing of
`paimon_tpu`."""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from chipbench.reference import Mismatch, _bits

INSERT, UPDATE_BEFORE, UPDATE_AFTER = 0, 1, 2
VALUES = ("v1", "v2", "v3")


@dataclass
class State:
    present: np.ndarray         # bool[key_space]
    v1: np.ndarray              # int64[key_space]
    v2: np.ndarray              # float64[key_space]
    v3: np.ndarray              # int32[key_space]

    def copy(self) -> "State":
        return State(self.present.copy(), self.v1.copy(), self.v2.copy(),
                     self.v3.copy())


def _last_per_key(ids: np.ndarray):
    """Sorted distinct keys and the position of each one's last row."""
    order = np.argsort(ids, kind="stable")
    sid = ids[order]
    last = np.concatenate([sid[1:] != sid[:-1], [True]])
    return sid[last], order[last]


def base_state(runs, key_space: int) -> State:
    """The table the runs build, in commit order, last write wins."""
    cols = {k: np.concatenate([r[k] for r in runs]) for k in runs[0]}
    keys, at = _last_per_key(cols["id"])
    state = State(np.zeros(key_space, bool), np.zeros(key_space, np.int64),
                  np.zeros(key_space, np.float64),
                  np.zeros(key_space, np.int32))
    state.present[keys] = True
    for v in VALUES:
        getattr(state, v)[keys] = cols[v][at]
    return state


def commit(state: State, cols) -> dict:
    """Fold one commit into `state`; its changelog, ordered by key and
    then kind (-U before +U): {id, v1, v2, v3, kind}."""
    keys, at = _last_per_key(cols["id"])
    was = state.present[keys]
    count = 1 + was.astype(np.int64)
    out = {"id": np.repeat(keys, count)}
    first = np.cumsum(count) - count          # each key's first row
    old = first[was]                          # the -U rows
    new = first + was                         # the +I / +U rows
    kind = np.full(len(out["id"]), INSERT, np.int8)
    kind[old] = UPDATE_BEFORE
    kind[new[was]] = UPDATE_AFTER
    out["kind"] = kind
    for v in VALUES:
        col = getattr(state, v)
        a = np.empty(len(out["id"]), col.dtype)
        a[old] = col[keys[was]]
        a[new] = cols[v][at]
        out[v] = a
        col[keys] = cols[v][at]
    state.present[keys] = True
    return out


def check_changelog(got: dict, want: dict, what: str):
    """`got` is the system's changelog of one commit as emitted ({id,
    v1, v2, v3, kind} in its own order): each -U sits right before the
    +U of its key, and ordered by key and kind it equals `want` row for
    row and bit for bit."""
    kind = got["kind"]
    ub = np.flatnonzero(kind == UPDATE_BEFORE)
    if len(ub) and (ub[-1] + 1 >= len(kind)
                    or np.any(kind[ub + 1] != UPDATE_AFTER)
                    or np.any(got["id"][ub + 1] != got["id"][ub])):
        raise Mismatch(f"{what}: a -U is not followed by the +U of its "
                       f"key")
    if len(kind) != len(want["kind"]):
        raise Mismatch(f"{what}: {len(kind)} changelog rows, reference "
                       f"has {len(want['kind'])}")
    order = np.lexsort((kind, got["id"]))
    for name, ref in want.items():
        have = got[name][order]
        if have.dtype != ref.dtype:
            raise Mismatch(f"{what}: {name} is {have.dtype}, reference "
                           f"{ref.dtype}")
        bad = np.flatnonzero(_bits(have) != _bits(ref))
        if len(bad):
            i = int(bad[0])
            raise Mismatch(f"{what}: {len(bad)} changelog rows differ in "
                           f"{name}; first at key {int(want['id'][i])} "
                           f"kind {int(want['kind'][i])}: got {have[i]!r}, "
                           f"reference {ref[i]!r}")


def check_table(got: dict, state: State, what: str):
    """The whole table ({id, v1, v2, v3}) equals the state."""
    keys = np.flatnonzero(state.present)
    if len(got["id"]) != len(keys):
        raise Mismatch(f"{what}: {len(got['id'])} rows, reference has "
                       f"{len(keys)}")
    order = np.argsort(got["id"], kind="stable")
    if np.any(got["id"][order] != keys):
        raise Mismatch(f"{what}: the keys differ")
    for v in VALUES:
        have, ref = got[v][order], getattr(state, v)[keys]
        if have.dtype != ref.dtype:
            raise Mismatch(f"{what}: {v} is {have.dtype}, reference "
                           f"{ref.dtype}")
        bad = np.flatnonzero(_bits(have) != _bits(ref))
        if len(bad):
            i = int(bad[0])
            raise Mismatch(f"{what}: {len(bad)} rows differ in {v}; first "
                           f"at key {int(keys[i])}: got {have[i]!r}, "
                           f"reference {ref[i]!r}")
