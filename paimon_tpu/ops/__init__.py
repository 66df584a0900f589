"""Device compute kernels (the TPU execution core).

This package replaces the reference's record-at-a-time merge machinery --
LoserTree (mergetree/compact/LoserTree.java:45), SortMergeReader
(SortMergeReaderWithLoserTree.java:34), MergeFunction implementations, and
Janino-generated comparators (paimon-codegen) -- with XLA-compiled
data-parallel kernels:

- normkey: memcmp-order-preserving key normalization into uint32 lanes
  (the BinaryRow "normalized key" idea, vectorized)
- merge: k-way sorted-run merge as one stable device sort over
  (key lanes, sequence) + segmented winner/reduce selection per merge
  engine; returns take-indices applied to Arrow on the host

Design notes: all kernels use static shapes (inputs padded to bucketized
sizes), uint32 lanes (TPU-native; 64-bit values split hi/lo), and
jnp-only control flow so XLA can fuse and tile freely.

Every entry point imports this package before it runs a kernel, so the
two process-wide JAX settings live here and nowhere else: 64-bit types,
and where the persistent compile cache is kept.
"""

import os as _os

import jax as _jax

# BIGINT columns aggregate in 64-bit (sum/max of int64 values); without
# x64, jax silently truncates to int32. TPU emulates int64 on the VPU --
# acceptable: the hot sort path uses uint32 lanes regardless.
_jax.config.update("jax_enable_x64", True)


def default_compile_cache_dir() -> str:
    """`<checkout>/.jax_cache`, derived from this package's own path.
    The directory is part of the cache key, so it must not move between
    runs: never a temp name, a pid or a time."""
    checkout = _os.path.dirname(_os.path.dirname(
        _os.path.dirname(_os.path.abspath(__file__))))
    return _os.path.join(checkout, ".jax_cache")


# JAX reads JAX_COMPILATION_CACHE_DIR itself; only when the operator has
# not placed the cache do we place it, at the one fixed path above.
if not _os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    _jax.config.update("jax_compilation_cache_dir",
                       default_compile_cache_dir())

from paimon_tpu.ops.normkey import NormalizedKeyEncoder  # noqa: F401,E402
from paimon_tpu.ops.merge import merge_runs, MergeResult  # noqa: F401,E402
