"""Pallas TPU kernels for the merge plane.

The segmented winner-select that follows the device sort is a chain of
elementwise neighbor comparisons over L lane vectors (ops/merge.py
segmented_merge_body): XLA emits it as several fused VPU loops over
HBM-resident operands.  This kernel fuses the WHOLE chain — L lane
equality compares, the validity guard and the boundary mask — into one
VMEM pass per (8, 128) tile, so each lane element is read from HBM
exactly once and the mask never materializes intermediate arrays.

Layout: 1-D arrays of padded length N (power of two >= 1024, as the
merge plane guarantees) are viewed as [N/128, 128] — the natural
(sublane, lane) tiling for 32-bit data — and the grid walks row blocks
of 8 sublanes.  The neighbor shift happens OUTSIDE the kernel (one XLA
roll), keeping every kernel operand block-aligned.

On non-TPU backends the kernel runs in interpret mode, so CPU tests
exercise the identical program; set PAIMON_DISABLE_PALLAS=1 to force
the plain XLA path.
"""

from __future__ import annotations

import os
from functools import lru_cache, partial
from typing import Sequence

import jax
import jax.numpy as jnp

__all__ = ["eq_next_mask", "pallas_enabled", "PALLAS_TILE"]

_BLOCK_ROWS = 8
_LANE = 128
PALLAS_TILE = _BLOCK_ROWS * _LANE     # N must be a multiple of this


def pallas_enabled() -> bool:
    """Kernel on for TPU (compiled) and cpu (interpret mode, so tests
    run the identical program); other accelerators keep the fused XLA
    path — interpret-emulating a grid there would be a regression."""
    if os.environ.get("PAIMON_DISABLE_PALLAS") == "1":
        return False
    return jax.default_backend() in ("tpu", "cpu")


# ovc_off value marking rows whose offset-value code is unusable (run
# starts: their predecessor is the -inf sentinel, not a real row) —
# keep in sync with ops/ovc.OVC_OFF_SENTINEL
_OVC_SENTINEL = 0xFFFFFFFF


@lru_cache(maxsize=16)
def _eq_next_fn(num_lanes: int, n: int, interpret: bool,
                with_ovc: bool = False, num_key_lanes: int = 0):
    from jax.experimental import pallas as pl

    rows = n // _LANE
    grid = (rows // _BLOCK_ROWS,)
    # the 0 column index MUST be pinned to int32: the package enables
    # jax x64 (ops/__init__.py) and a weak `0` traces to i64, giving
    # the index map a mixed (i32, i64) signature that Mosaic rejects
    # ("failed to legalize operation 'func.return'") on real TPUs
    spec = pl.BlockSpec((_BLOCK_ROWS, _LANE),
                        lambda i: (i, jnp.int32(0)))

    def kernel(*refs):
        # refs: cur lanes... nxt lanes... inv_cur, inv_nxt,
        #       [off_nxt, perm_cur, perm_nxt,] out
        cur = refs[:num_lanes]
        nxt = refs[num_lanes:2 * num_lanes]
        inv_cur = refs[2 * num_lanes]
        inv_nxt = refs[2 * num_lanes + 1]
        out = refs[-1]
        eq = cur[0][...] == nxt[0][...]
        for l in range(1, num_lanes):
            eq = jnp.logical_and(eq, cur[l][...] == nxt[l][...])
        if with_ovc:
            # single-int offset-value codes first: a sorted-adjacent
            # pair that is also run-consecutive resolves key equality
            # from the next row's code alone (offset past the key
            # lanes = same key); only the remaining pairs use the full
            # lane-compare chain above
            off_nxt = refs[2 * num_lanes + 2]
            perm_cur = refs[2 * num_lanes + 3]
            perm_nxt = refs[2 * num_lanes + 4]
            consec = perm_nxt[...] == perm_cur[...] + 1
            known = off_nxt[...] != jnp.uint32(_OVC_SENTINEL)
            eq_code = off_nxt[...] >= jnp.uint32(num_key_lanes)
            # the select runs on 32-bit words: Mosaic (libtpu 0.0.34)
            # refuses a select whose OPERANDS are i1 vectors
            # ("Unsupported target bitwidth for truncation", i8 -> i1)
            eq = jnp.where(jnp.logical_and(consec, known),
                           eq_code.astype(jnp.uint32),
                           eq.astype(jnp.uint32)) != jnp.uint32(0)
        eq = jnp.logical_and(eq, inv_cur[...] == inv_nxt[...])
        out[...] = eq.astype(jnp.uint32)

    n_in = 2 * num_lanes + 2 + (3 if with_ovc else 0)
    fn = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[spec] * n_in,
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((rows, _LANE), jnp.uint32),
        interpret=interpret,
    )

    def run(lane_list, invalid, ovc_off=None, perm=None):
        def shaped(a):
            return a.reshape(rows, _LANE)

        def shifted(a):
            return shaped(jnp.roll(a, -1))

        args = ([shaped(a) for a in lane_list]
                + [shifted(a) for a in lane_list]
                + [shaped(invalid), shifted(invalid)])
        if with_ovc:
            args += [shifted(ovc_off), shaped(perm), shifted(perm)]
        eq = fn(*args).reshape(n)
        # the final element wraps around to position 0: never a segment
        # continuation
        return eq.at[n - 1].set(0).astype(jnp.bool_)

    return run


def _eq_next_xla(lane_list, invalid, ovc_off=None, perm=None,
                 num_key_lanes: int = 0):
    lanes_mat = jnp.stack(list(lane_list))
    eq = jnp.all(lanes_mat[:, :-1] == lanes_mat[:, 1:], axis=0)
    if ovc_off is not None:
        consec = perm[1:] == perm[:-1] + 1
        known = ovc_off[1:] != jnp.uint32(_OVC_SENTINEL)
        eq_code = ovc_off[1:] >= jnp.uint32(num_key_lanes)
        eq = jnp.where(consec & known, eq_code, eq)
    eq = eq & (invalid[:-1] == invalid[1:])
    return jnp.concatenate([eq, jnp.array([False])])


def eq_next_mask(lane_list: Sequence[jnp.ndarray],
                 invalid: jnp.ndarray,
                 ovc_off: jnp.ndarray = None,
                 perm: jnp.ndarray = None) -> jnp.ndarray:
    """bool[N]: position i continues the same (validity, lanes...)
    segment at i+1.  Fused Pallas pass on tpu/cpu backends for
    tile-aligned N; every other case takes the equivalent XLA ops, so
    callers never need their own shape/backend gate.

    `ovc_off`/`perm` (sorted-order offset-value-code offsets + the sort
    permutation) switch on the single-int-code fast path: pairs whose
    codes decide key equality skip the lane-compare chain, the rest
    fall through to it (ops/ovc.run_ovc_offsets documents the code)."""
    n = invalid.shape[0]
    num_key_lanes = len(lane_list)
    if n == 0 or n % PALLAS_TILE != 0 or not pallas_enabled():
        return _eq_next_xla(lane_list, invalid, ovc_off, perm,
                            num_key_lanes)
    interpret = jax.default_backend() != "tpu"
    run = _eq_next_fn(len(lane_list), n, interpret,
                      with_ovc=ovc_off is not None,
                      num_key_lanes=num_key_lanes)
    if ovc_off is not None:
        return run(list(lane_list), invalid, ovc_off, perm)
    return run(list(lane_list), invalid)
