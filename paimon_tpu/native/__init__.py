"""Native (C) runtime components, loaded through ctypes.

The hot host-side sort of the merge plane compiles from
`radix_sort.c` on first use (gcc/cc, -O3) into a cached shared object
next to this file; everything degrades gracefully to the numpy path
when no compiler is available or PAIMON_DISABLE_NATIVE=1.

The shared object's NAME carries a hash of the sources and the compile
flags, so the only library this package ever loads is one built from
the sources it sits next to: a copied tree (file times mean nothing
there) or an edited source simply misses the cache and rebuilds.

This is the framework's native-runtime layer in the sense of the
reference's C/JVM-intrinsic sort machinery (paimon-core
sort/BinaryInMemorySortBuffer, codegen'd comparators): Python stays
the control plane, the per-row inner loops live in C.
"""

import ctypes
import hashlib
import os
import shutil
import subprocess
import sys
import tempfile
from typing import Optional

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
# every .c in this package compiles into ONE shared object; keep the
# list explicit so the build (and the tier-1 build-smoke test) cannot
# silently miss a new source file
SOURCES = ("radix_sort.c", "probe.c")
_SRCS = tuple(os.path.join(_DIR, s) for s in SOURCES)
_CFLAGS = ("-O3", "-shared", "-fPIC")

# symbols the ctypes wrappers bind; a library lacking any of them fails
# the whole load (it cannot have been built from these sources)
REQUIRED_SYMBOLS = ("radix_argsort_u64", "merge_winners_u64",
                    "ovc_codes_u64", "ovc_codes_lanes",
                    "ovc_merge_u64", "ovc_merge_lanes",
                    "sst_probe_batch")

_lib: Optional[ctypes.CDLL] = None
_lib_path: Optional[str] = None
_tried = False


def lib_name() -> str:
    """`_paimon_native-<hash>.so`: the hash covers every source file's
    bytes and the compile flags."""
    h = hashlib.sha256(" ".join(_CFLAGS).encode())
    for src in _SRCS:
        with open(src, "rb") as f:
            h.update(b"\0" + os.path.basename(src).encode() + b"\0")
            h.update(f.read())
    return f"_paimon_native-{h.hexdigest()[:16]}.so"


def loaded_path() -> Optional[str]:
    """File the loaded library came from; None when none is loaded."""
    return _lib_path


def _compiler():
    for cc in (os.environ.get("CC"), "cc", "gcc", "clang"):
        if cc and shutil.which(cc):
            return cc
    return None


def _compile(cc: str, out: str) -> Optional[str]:
    """Compile every source into `out` (atomically, via a sibling temp
    name); returns None on success, else the failure text."""
    tmp = out + f".build-{os.getpid()}"
    try:
        proc = subprocess.run([cc, *_CFLAGS, "-o", tmp, *_SRCS],
                              capture_output=True, text=True,
                              timeout=120)
        if proc.returncode != 0:
            return proc.stderr[-1000:]
        os.replace(tmp, out)         # atomic vs concurrent builders
        return None
    except (OSError, subprocess.TimeoutExpired) as e:
        return str(e)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)


def _build(cc: str, use_cache: bool = True) -> Optional[str]:
    """Path of the hash-named shared object, compiling it unless a file
    of that name is already cached; prefer caching it next to the
    source, fall back to a temp dir when the package dir is not
    writable.  The last failure's stderr is reported only if every
    location fails."""
    name = lib_name()
    errors = []
    for make_dir in (lambda: _DIR,
                     lambda: tempfile.mkdtemp(prefix="paimon_native_")):
        out = os.path.join(make_dir(), name)
        if use_cache and os.path.exists(out):
            return out
        error = _compile(cc, out)
        if error is None:
            return out
        errors.append(error)         # e.g. read-only dir: try the next
    sys.stderr.write(f"paimon_tpu.native: build failed:\n"
                     f"{errors[-1]}\n")
    return None


def load() -> Optional[ctypes.CDLL]:
    """The native library, building it on first use; None when
    unavailable (no compiler / disabled / build failure)."""
    global _lib, _lib_path, _tried
    if _lib is not None or _tried:
        return _lib
    _tried = True
    if os.environ.get("PAIMON_DISABLE_NATIVE") == "1":
        return None
    cc = _compiler()
    if cc is None:
        return None
    lib = None
    for use_cache in (True, False):
        path = _build(cc, use_cache=use_cache)
        if path is None:
            return None
        try:
            lib = ctypes.CDLL(path)
            break
        # lint-ok: fault-taxonomy deterministic local recovery, not a
        # store retry: a cached .so from another platform/arch fails
        # to dlopen, so drop the cache and compile fresh exactly once
        except OSError:
            lib = None
            continue
    if lib is None:
        return None
    i64 = ctypes.c_int64
    p_u64 = np.ctypeslib.ndpointer(np.uint64, flags="C_CONTIGUOUS")
    p_i64 = np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS")
    p_i32 = np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS")
    p_u8 = np.ctypeslib.ndpointer(np.uint8, flags="C_CONTIGUOUS")
    lib.radix_argsort_u64.argtypes = [p_u64, i64, p_i32]
    lib.radix_argsort_u64.restype = ctypes.c_int
    lib.merge_winners_u64.argtypes = [p_u64, p_i64, i64, ctypes.c_int,
                                      p_i32, p_u8]
    lib.merge_winners_u64.restype = ctypes.c_int
    p_u32 = np.ctypeslib.ndpointer(np.uint32, flags="C_CONTIGUOUS")
    lib.ovc_codes_u64.argtypes = [p_u64, p_i64, p_i64, i64, p_u64]
    lib.ovc_codes_u64.restype = ctypes.c_int
    lib.ovc_codes_lanes.argtypes = [p_u32, p_i64, p_i64, i64, i64,
                                    p_u64]
    lib.ovc_codes_lanes.restype = ctypes.c_int
    lib.ovc_merge_u64.argtypes = [p_u64, p_i64, p_u64, p_i64, i64, i64,
                                  p_i32, p_u64]
    lib.ovc_merge_u64.restype = ctypes.c_int
    lib.ovc_merge_lanes.argtypes = [p_u32, p_i64, p_u64, p_i64, i64,
                                    i64, i64, p_i32, p_u64]
    lib.ovc_merge_lanes.restype = ctypes.c_int
    lib.sst_probe_batch.argtypes = [p_u8, i64, i64, p_u64, i64, i64,
                                    p_u8, p_u64, i64, p_i64, p_i64]
    lib.sst_probe_batch.restype = ctypes.c_int
    _lib, _lib_path = lib, path
    return _lib


_predicted: Optional[bool] = None


def predicted_available() -> bool:
    """Will the native sort (eventually) be available in this process?
    Cheap memoized predicate for cost models that must not trigger the
    build: loaded lib -> True; PAIMON_DISABLE_NATIVE/no compiler ->
    False; otherwise a compiler on PATH means the lazy build will
    succeed with overwhelming likelihood."""
    global _predicted
    if _lib is not None:
        return True
    if _tried:
        return False                 # load attempted and failed
    if os.environ.get("PAIMON_DISABLE_NATIVE") == "1":
        return False                 # env read fresh — tests toggle it
    if _predicted is None:
        _predicted = _compiler() is not None   # PATH probe only
    return _predicted


def radix_argsort(keys: np.ndarray) -> Optional[np.ndarray]:
    """Stable ascending argsort of uint64 keys via the C radix sort;
    None when the native library is unavailable (caller falls back)."""
    lib = load()
    if lib is None:
        return None
    keys = np.ascontiguousarray(keys, dtype=np.uint64)
    perm = np.empty(len(keys), dtype=np.int32)
    if lib.radix_argsort_u64(keys, len(keys), perm) != 0:
        return None
    return perm


def ovc_codes_u64(keys: np.ndarray, seq: np.ndarray,
                  starts: np.ndarray) -> Optional[np.ndarray]:
    """Initial per-run offset-value codes for packed u64 keys (two
    logical big-endian u32 lanes), or None when the native library is
    unavailable OR any run violates its (key, seq) ascending sort
    contract — exposed for the code-semantics tests; ovc_merge_u64
    runs this same C pass internally."""
    lib = load()
    if lib is None:
        return None
    keys = np.ascontiguousarray(keys, dtype=np.uint64)
    seq = np.ascontiguousarray(seq, dtype=np.int64)
    starts = np.ascontiguousarray(starts, dtype=np.int64)
    codes = np.empty(len(keys), dtype=np.uint64)
    if lib.ovc_codes_u64(keys, seq, starts, len(starts) - 1,
                         codes) != 0:
        return None
    return codes


def ovc_codes_lanes(lanes: np.ndarray, seq: np.ndarray,
                    starts: np.ndarray) -> Optional[np.ndarray]:
    """Lane-matrix variant of ovc_codes_u64."""
    lib = load()
    if lib is None:
        return None
    lanes = np.ascontiguousarray(lanes, dtype=np.uint32)
    seq = np.ascontiguousarray(seq, dtype=np.int64)
    starts = np.ascontiguousarray(starts, dtype=np.int64)
    codes = np.empty(lanes.shape[0], dtype=np.uint64)
    if lib.ovc_codes_lanes(lanes, seq, starts, len(starts) - 1,
                           lanes.shape[1], codes) != 0:
        return None
    return codes


def ovc_merge_u64(keys: np.ndarray, seq: np.ndarray,
                  starts: np.ndarray) -> Optional[tuple]:
    """Offset-value coded k-way merge of sorted runs over packed u64
    keys: one C pass computes the initial per-run codes (verifying the
    (key, seq) sort contract), a second runs the single-int-compare
    merge.  Returns (perm, code_out) in merged order, or None when the
    native library is unavailable or a run violates its contract (the
    caller falls back to the sort paths)."""
    lib = load()
    if lib is None:
        return None
    keys = np.ascontiguousarray(keys, dtype=np.uint64)
    seq = np.ascontiguousarray(seq, dtype=np.int64)
    starts = np.ascontiguousarray(starts, dtype=np.int64)
    n = len(keys)
    k = len(starts) - 1
    codes = np.empty(n, dtype=np.uint64)
    if lib.ovc_codes_u64(keys, seq, starts, k, codes) != 0:
        return None
    perm = np.empty(n, dtype=np.int32)
    code = np.empty(n, dtype=np.uint64)
    if lib.ovc_merge_u64(keys, seq, codes, starts, k, n,
                         perm, code) != 0:
        return None
    return perm, code


def ovc_merge_lanes(lanes: np.ndarray, seq: np.ndarray,
                    starts: np.ndarray) -> Optional[tuple]:
    """Lane-matrix variant of ovc_merge_u64 for multi-lane normalized
    keys (wide/composite/string-prefix keys)."""
    lib = load()
    if lib is None:
        return None
    lanes = np.ascontiguousarray(lanes, dtype=np.uint32)
    seq = np.ascontiguousarray(seq, dtype=np.int64)
    starts = np.ascontiguousarray(starts, dtype=np.int64)
    n, num_lanes = lanes.shape
    k = len(starts) - 1
    codes = np.empty(n, dtype=np.uint64)
    if lib.ovc_codes_lanes(lanes, seq, starts, k, num_lanes,
                           codes) != 0:
        return None
    perm = np.empty(n, dtype=np.int32)
    code = np.empty(n, dtype=np.uint64)
    if lib.ovc_merge_lanes(lanes, seq, codes, starts, k,
                           n, num_lanes, perm, code) != 0:
        return None
    return perm, code


def sst_probe(flat_keys: np.ndarray, n_rows: int, key_width: int,
              bloom_bits: Optional[np.ndarray], bloom_k: int,
              qkeys: np.ndarray, qhashes: np.ndarray
              ) -> Optional[tuple]:
    """Batched SST probe: bloom + binary search over the flat sorted
    key buffer, one C call for the whole query batch.  Returns the per
    query row ranges (lo int64[m], hi int64[m]; lo==hi is a miss,
    -1/-1 a bloom rejection), or None when the native library is
    unavailable (the caller falls back to the Python path and counts
    it)."""
    lib = load()
    if lib is None:
        return None
    if bloom_bits is None:
        bloom_bits = np.zeros(0, dtype=np.uint64)
        bloom_k = 0
    m = len(qhashes)
    lo = np.empty(m, dtype=np.int64)
    hi = np.empty(m, dtype=np.int64)
    if lib.sst_probe_batch(
            np.ascontiguousarray(flat_keys, dtype=np.uint8),
            int(n_rows), int(key_width),
            np.ascontiguousarray(bloom_bits, dtype=np.uint64),
            len(bloom_bits), int(bloom_k),
            np.ascontiguousarray(qkeys, dtype=np.uint8),
            np.ascontiguousarray(qhashes, dtype=np.uint64),
            m, lo, hi) != 0:
        return None
    return lo, hi


_RAW_PROBE = None


def _raw_probe():
    """`sst_probe_batch` re-bound through a raw CFUNCTYPE taking
    c_void_p arguments: skips the per-call ndpointer from_param
    validation, which at serving batch sizes (a handful of keys per
    probe) rivals the binary search itself.  CFUNCTYPE foreign calls
    release the GIL like CDLL ones."""
    global _RAW_PROBE
    if _RAW_PROBE is None:
        lib = load()
        if lib is None:
            _RAW_PROBE = False
        else:
            addr = ctypes.cast(lib.sst_probe_batch,
                               ctypes.c_void_p).value
            i64 = ctypes.c_int64
            vp = ctypes.c_void_p
            proto = ctypes.CFUNCTYPE(ctypes.c_int, vp, i64, i64, vp,
                                     i64, i64, vp, vp, i64, vp, vp)
            _RAW_PROBE = proto(addr)
    return _RAW_PROBE or None


def sst_probe_prepare(flat_keys: np.ndarray, n_rows: int,
                      key_width: int,
                      bloom_bits: Optional[np.ndarray],
                      bloom_k: int) -> Optional[tuple]:
    """Pin an SST's static probe arguments (flat key buffer + bloom
    words) as raw pointers, resolved ONCE per reader; pass the result
    to `sst_probe_prepared` per batch.  Returns None when the native
    probe is unavailable (caller keeps using `sst_probe`, which then
    reports the fallback)."""
    fn = _raw_probe()
    if fn is None:
        return None
    fk = np.ascontiguousarray(flat_keys, dtype=np.uint8)
    bb = np.ascontiguousarray(bloom_bits, dtype=np.uint64) \
        if bloom_bits is not None else np.zeros(0, dtype=np.uint64)
    # the trailing array refs keep the pinned buffers alive as long as
    # the prep tuple (the raw pointers dangle otherwise)
    return (fn, fk.ctypes.data, int(n_rows), int(key_width),
            bb.ctypes.data, len(bb), int(bloom_k), (fk, bb))


def sst_probe_prepared(prep: tuple, qkeys: np.ndarray,
                       qhashes: np.ndarray) -> Optional[tuple]:
    """`sst_probe` over a `sst_probe_prepare` context: only the query
    arrays cross the boundary per call.

    lo/hi share ONE scratch allocation and every pointer comes from
    `__array_interface__` — `.ctypes.data` builds a ctypes view object
    per access, which at one-or-two-key probes costs as much as the
    search itself."""
    fn, fk_ptr, n_rows, kw, bb_ptr, bb_len, bk, _pin = prep
    qk = np.ascontiguousarray(qkeys, dtype=np.uint8)
    qh = np.ascontiguousarray(qhashes, dtype=np.uint64)
    m = len(qh)
    res = np.empty(2 * m, dtype=np.int64)
    base = res.__array_interface__["data"][0]
    if fn(fk_ptr, n_rows, kw, bb_ptr, bb_len, bk,
          qk.__array_interface__["data"][0],
          qh.__array_interface__["data"][0], m,
          base, base + 8 * m) != 0:
        return None
    return res[:m], res[m:]


def build_fresh(out_dir: str) -> Optional[str]:
    """Compile every native source from scratch into `out_dir` (no
    cache, package dir untouched) — the tier-1 build-smoke test uses
    this to prove the sources still compile and export every bound
    symbol.  Returns the .so path or None (no compiler/failed)."""
    cc = _compiler()
    if cc is None:
        return None
    out = os.path.join(out_dir, lib_name())
    return out if _compile(cc, out) is None else None


def merge_winners(keys: np.ndarray, seq: np.ndarray, keep_last: bool
                  ) -> Optional[tuple]:
    """(perm, winner_mask_in_sorted_order) via the fused C path, or
    None when unavailable."""
    lib = load()
    if lib is None:
        return None
    keys = np.ascontiguousarray(keys, dtype=np.uint64)
    seq = np.ascontiguousarray(seq, dtype=np.int64)
    n = len(keys)
    perm = np.empty(n, dtype=np.int32)
    winner = np.empty(n, dtype=np.uint8)
    if lib.merge_winners_u64(keys, seq, n, int(keep_last), perm,
                             winner) != 0:
        return None
    return perm, winner.view(bool)
