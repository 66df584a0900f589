"""Point probes of key-sorted runs on the device.

The lookup changelog producer asks, for every key a commit touched,
whether a run above level 0 holds it and at which row (reference
LookupLevels.lookup).  A run's keys stay on the device as normalized
key lanes (`ops/normkey.py`, the lanes every merge sorts), lane-major
(`uint32[L, capacity]`), and one program answers a batch of probes by
a search of two levels, as a B-tree of one inner node would:

- the fences, the first row of each block of `BLOCK` rows (a column of
  the run viewed as blocks, taken in the program), are sorted with the probes
  (a probe after the fences of equal lanes); a running count of the
  fences gives each probe the fences below it and those not above it,
  and a second sort, by the probes' own order, brings the counts back;
- each probe then reads the one block the count names, `BLOCK`
  contiguous words a lane, and counts its rows below the probe (and
  not above it): the block's first row plus that count is the bound.

Both sorts span the fences and the probes, not the run, and the block
read is one gather of a probe's whole block, every lane at once.  (A
binary search gathers one scattered word per probe and step, which a
TPU does far slower than it sorts; a sort of the whole run with the
probes reads and moves every row of it on every call.)

`lookup_probe` returns each probe's row, or -1: the first row whose
lanes are not below the probe's, where its lanes are equal.  Lanes
determine a key unless the encoder cut it (`truncated`); for such keys
the program also returns the end of the run of equal lanes, which may
span blocks, and the caller confirms the candidates by their full bytes
(`lookup/levels_index.py`, through `ops/merge.py` `tiebreak_cut_keys`).

Shapes are padded to powers of four (at least `MIN_CAPACITY`, a
multiple of `BLOCK`), so runs and batches of a similar size share one
program; a run's real length is an operand.
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
BLOCK = 128             # rows a fence stands for: the vector lane width
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
    value (the probe leaves a run's rows from `n` on out; a batch's
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


def _counts(blocks, queries, block, n):
    """Rows of each probe's block (`block` int32[P]) below the probe
    and not above it, rows from `n` on left out."""
    # one gather of whole blocks, every lane of a block at once
    rows = blocks.at[:, block].get(mode="promise_in_bounds")   # [L, P, B]
    below = jnp.zeros(rows.shape[1:], dtype=bool)
    equal = jnp.ones(rows.shape[1:], dtype=bool)
    for lane in range(rows.shape[0]):
        key = queries[lane][:, None]
        below = below | (equal & (rows[lane] < key))
        equal = equal & (rows[lane] == key)
    real = block[:, None] * BLOCK + jnp.arange(BLOCK, dtype=jnp.int32) < n
    return (jnp.sum(real & below, axis=1, dtype=jnp.int32),
            jnp.sum(real & (below | equal), axis=1, dtype=jnp.int32))


@partial(jax.jit, static_argnames=("upper",))
def lookup_probe(index, n, queries, upper: bool = False):
    """`index` uint32[L, cap] (rows from `n` on are padding), `n` int32,
    `queries` uint32[L, P].  Returns int32[P] rows (-1: no row with the
    probe's lanes) and, with `upper`, int32[P] ends of the runs of
    equal lanes."""
    num_lanes, cap = index.shape
    assert cap % BLOCK == 0, cap
    num_fences = cap // BLOCK
    p = queries.shape[1]
    blocks = index.reshape(num_lanes, num_fences, BLOCK)
    # one int32 tag orders fences of equal lanes: the run's (0), then
    # the probes (1 + their position), then the padding's (last)
    tag = jnp.concatenate([
        jnp.where(jnp.arange(num_fences, dtype=jnp.int32) * BLOCK < n, 0,
                  _LAST),
        jnp.arange(1, p + 1, dtype=jnp.int32)])
    operands = [jnp.concatenate([blocks[lane, :, 0], queries[lane]])
                for lane in range(num_lanes)]
    # the keys order every element that is read: neither sort need be
    # stable (which would cost one more operand)
    *lanes, tag = jax.lax.sort((*operands, tag), num_keys=num_lanes + 1,
                               is_stable=False)
    is_fence = tag == 0
    upto = jnp.cumsum(is_fence.astype(jnp.int32))   # fences not above
    same = jnp.ones(upto.shape[0] - 1, dtype=bool)
    for lane in lanes:
        same = same & (lane[1:] == lane[:-1])
    start = jnp.concatenate([jnp.ones(1, dtype=bool), ~same])
    under = jax.lax.cummax(jnp.where(start, upto - is_fence, 0))
    probe_at = jnp.where((tag > 0) & (tag < _LAST), tag - 1, _LAST)
    _, under, upto = jax.lax.sort((probe_at, under, upto), num_keys=1,
                                  is_stable=False)
    under, upto = under[:p], upto[:p]
    # the lower bound lies in the block of the last fence below the
    # probe, or is the first row of the next, a fence equal to it
    first = jnp.maximum(under - 1, 0)
    below, not_above = _counts(blocks, queries, first, n)
    rows = jnp.where((not_above > below) | (upto > under),
                     first * BLOCK + below, -1)
    if not upper:
        return rows
    # equal lanes may span blocks: their end lies in the block of the
    # last fence not above the probe
    last = jnp.maximum(upto - 1, 0)
    _, not_above = _counts(blocks, queries, last, n)
    return rows, last * BLOCK + not_above


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
