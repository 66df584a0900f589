"""Point probes of key-sorted runs on the device.

The lookup changelog producer asks, for every key a commit touched,
whether a run above level 0 holds it and at which row (reference
LookupLevels.lookup).  A run's keys stay on the device as normalized
key lanes (`ops/normkey.py`, the lanes every merge sorts), lane-major
(`uint32[L, capacity]`), and one program answers a batch of probes by
merging them into the run: one sort of the run's lanes and the probes'
(a probe after the run's rows of equal lanes), a running count of the
run's rows, and the count at the start of each group of equal lanes,
which is the probe's lower bound; a second sort, by the probes' own
order, brings the answers back.  (A binary search gathers one row per
probe and step, and a TPU gathers scattered words far slower than it
sorts: the sort is what `jnp.searchsorted(method="sort")` does there.)

`lookup_probe` returns each probe's row, or -1: the first row whose
lanes are not below the probe's, where its lanes are equal.  Lanes
determine a key unless the encoder cut it (`truncated`); for such keys
the program also returns the end of the run of equal lanes, and the
caller confirms the candidates by their full bytes
(`lookup/levels_index.py`, through `ops/merge.py` `tiebreak_cut_keys`).

Shapes are padded to powers of four (at least `MIN_CAPACITY`), so runs
and batches of a similar size share one program; a run's real length
is an operand.
"""

from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["MIN_CAPACITY", "capacity", "device_lanes", "lookup_probe",
           "probe"]

MIN_CAPACITY = 1024
_LAST = np.iinfo(np.int32).max


def capacity(n: int) -> int:
    """The padded length of `n` rows: the next power of four, at least
    `MIN_CAPACITY`."""
    cap = MIN_CAPACITY
    while cap < n:
        cap *= 4
    return cap


def _lane_major(lanes: np.ndarray, cap: int) -> np.ndarray:
    """`uint32[n, L]` -> `uint32[L, cap]`, padded with the largest lane
    value (a run's padding sorts after every row and probe; a batch's
    padding gives answers nobody reads)."""
    lanes = np.asarray(lanes, dtype=np.uint32)
    n, num_lanes = lanes.shape
    out = np.full((num_lanes, cap), np.uint32(0xFFFFFFFF), np.uint32)
    out[:, :n] = lanes.T
    return out


def device_lanes(lanes: np.ndarray) -> jax.Array:
    """A run's key lanes (`uint32[n, L]`, key order) as the resident
    device array the probe reads."""
    return jax.device_put(_lane_major(lanes, capacity(len(lanes))))


@partial(jax.jit, static_argnames=("upper",))
def lookup_probe(index, n, queries, upper: bool = False):
    """`index` uint32[L, cap] (rows from `n` on are padding), `n` int32,
    `queries` uint32[L, P].  Returns int32[P] rows (-1: no row with the
    probe's lanes) and, with `upper`, int32[P] ends of the runs of
    equal lanes."""
    num_lanes, cap = index.shape
    p = queries.shape[1]
    # one int32 tag orders rows of equal lanes: the run's (0), then the
    # probes (1 + their position), then the padding (last)
    tag = jnp.concatenate([
        jnp.where(jnp.arange(cap, dtype=jnp.int32) < n, 0, _LAST),
        jnp.arange(1, p + 1, dtype=jnp.int32)])
    operands = [jnp.concatenate([index[lane], queries[lane]])
                for lane in range(num_lanes)]
    *lanes, tag = jax.lax.sort((*operands, tag), num_keys=num_lanes + 1)
    is_row = tag == 0
    count = jnp.cumsum(is_row.astype(jnp.int32))    # run rows up to here
    same = jnp.ones(count.shape[0] - 1, dtype=bool)
    for lane in lanes:
        same = same & (lane[1:] == lane[:-1])
    start = jnp.concatenate([jnp.ones(1, dtype=bool), ~same])
    # the run's rows before the group of equal lanes: the lower bound
    below = jax.lax.cummax(jnp.where(start, count - is_row, 0))
    rows = jnp.where(count > below, below, -1)
    probe_at = jnp.where((tag > 0) & (tag < _LAST), tag - 1, _LAST)
    out = jax.lax.sort((probe_at, rows, count) if upper
                       else (probe_at, rows), num_keys=1)
    if upper:
        return out[1][:p], out[2][:p]
    return out[1][:p]


def probe(index: jax.Array, n: int, lanes: np.ndarray,
          upper: bool = False) -> Tuple[np.ndarray, ...]:
    """Probe `lanes` (`uint32[P, L]`) against a resident run of `n`
    rows: one upload, one program, one download.  Returns
    `(rows, ends)`, `ends` None unless `upper`."""
    p = len(lanes)
    queries = jax.device_put(_lane_major(lanes, capacity(p)))
    out = lookup_probe(index, np.int32(n), queries, upper=upper)
    if upper:
        rows, ends = jax.device_get(out)
        return np.asarray(rows[:p]), np.asarray(ends[:p])
    return np.asarray(jax.device_get(out)[:p]), None
