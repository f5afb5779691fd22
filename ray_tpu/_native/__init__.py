"""Native (C++) kernels with transparent build + pure-numpy fallback.

hashing.cpp is compiled once per machine with g++ -O3 into a cached .so
(keyed by source hash, in the git-ignored ``_build`` directory beside it) and bound via ctypes —
no pybind11 dependency. If no compiler is available the numpy fallbacks
keep everything working (slower on string keys).

    from ray_tpu._native import hash_column, partition_indices
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading

import numpy as np

_HERE = os.path.dirname(__file__)
_SRC = os.path.join(_HERE, "hashing.cpp")
_lock = threading.Lock()
_lib = None
_lib_tried = False

MAX_PARTITIONS = 4096  # partition_gather's stack cursor bound


def _build() -> "ctypes.CDLL | None":
    try:
        with open(_SRC, "rb") as f:
            digest = hashlib.sha256(f.read()).hexdigest()[:16]
        cache = os.path.join(_HERE, "_build")
        os.makedirs(cache, exist_ok=True)
        so = os.path.join(cache, f"hashing_{digest}.so")
        if not os.path.exists(so):
            tmp = f"{so}.{os.getpid()}.tmp"
            subprocess.run(
                ["g++", "-O3", "-shared", "-fPIC", "-std=c++17", _SRC, "-o", tmp],
                check=True,
                capture_output=True,
                timeout=120,
            )
            os.replace(tmp, so)
        lib = ctypes.CDLL(so)
        lib.hash_u64.argtypes = [ctypes.c_void_p, ctypes.c_int64, ctypes.c_void_p]
        lib.hash_bytes_rows.argtypes = [ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64, ctypes.c_void_p]
        lib.hash_combine.argtypes = [ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64]
        lib.partition_assign.argtypes = [ctypes.c_void_p, ctypes.c_int64, ctypes.c_int32, ctypes.c_void_p, ctypes.c_void_p]
        lib.partition_gather.argtypes = [ctypes.c_void_p, ctypes.c_int64, ctypes.c_int32, ctypes.c_void_p, ctypes.c_void_p]
        return lib
    except Exception:
        return None


def get_lib():
    global _lib, _lib_tried
    if not _lib_tried:
        with _lock:
            if not _lib_tried:
                _lib = _build()
                _lib_tried = True
    return _lib


def native_available() -> bool:
    return get_lib() is not None


def _ptr(a: np.ndarray):
    return a.ctypes.data_as(ctypes.c_void_p)


_NULL_SENTINEL = "\x00__rt_null__\x00"
_FNV_OFFSET = 1469598103934665603
_FNV_PRIME = 1099511628211
_U64 = (1 << 64) - 1


def _fnv1a_py(data: bytes) -> int:
    h = _FNV_OFFSET
    for b in data:
        h = ((h ^ b) * _FNV_PRIME) & _U64
    return h


def hash_column(col) -> np.ndarray:
    """uint64 hashes for one key column.

    Accepts a numpy array (numeric) or a pyarrow Array/ChunkedArray
    (numeric or string/binary). EVERY path — native or fallback, sliced
    or null-bearing arrays — produces identical hash values (FNV-1a over
    utf-8 bytes for strings, splitmix64 for numerics), so shuffle bucket
    assignment can never diverge between blocks/processes."""
    import pyarrow as pa

    if isinstance(col, pa.ChunkedArray):
        col = col.combine_chunks()
    lib = get_lib()
    if isinstance(col, pa.Array):
        if pa.types.is_string(col.type) or pa.types.is_binary(col.type):
            import pyarrow.compute as pc

            if col.null_count:
                col = pc.fill_null(col, _NULL_SENTINEL)
            if col.offset != 0:
                # compact a sliced array so its buffers start at 0
                col = col.take(pa.array(np.arange(len(col), dtype=np.int64)))
            if lib is not None:
                offsets = np.frombuffer(col.buffers()[1], dtype=np.int32, count=len(col) + 1)
                nbytes = int(offsets[-1])
                data = (
                    np.frombuffer(col.buffers()[2], dtype=np.uint8, count=nbytes)
                    if nbytes
                    else np.zeros(0, np.uint8)
                )
                out = np.empty(len(col), np.uint64)
                lib.hash_bytes_rows(_ptr(offsets), _ptr(data), len(col), _ptr(out))
                return out
            # fallback: SAME FNV-1a, in python (slow but identical values)
            return np.asarray(
                [_fnv1a_py(v if isinstance(v, bytes) else str(v).encode()) for v in col.to_pylist()],
                np.uint64,
            )
        col = np.asarray(col)
    col = np.asarray(col)
    if col.dtype.kind == "f":
        # hash the FLOAT BIT PATTERN (hashing.cpp's contract): astype(int64)
        # would truncate every fractional float in [n, n+1) onto one hash.
        # Normalize -0.0 -> +0.0 (they compare equal) and NaN payloads to
        # one canonical NaN so equal keys hash equally.
        f = np.ascontiguousarray(col).astype(np.float64, copy=False) + 0.0
        f = np.where(np.isnan(f), np.float64("nan"), f)
        keys = f.view(np.uint64)
    elif col.dtype.kind in "iu":
        keys = np.ascontiguousarray(col).astype(np.int64, copy=False).view(np.uint64)
    else:
        # generic objects: FNV-1a over the str form — deterministic across
        # processes (unlike builtin hash(), which is salted per process)
        return np.asarray([_fnv1a_py(str(v).encode()) for v in col.tolist()], np.uint64)
    if lib is not None:
        out = np.empty(len(keys), np.uint64)
        lib.hash_u64(_ptr(np.ascontiguousarray(keys)), len(keys), _ptr(out))
        return out
    # numpy splitmix64
    x = keys + np.uint64(0x9E3779B97F4A7C15)
    x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return x ^ (x >> np.uint64(31))


def combine_hashes(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    lib = get_lib()
    a = np.ascontiguousarray(a, np.uint64)
    if lib is not None:
        out = a.copy()
        lib.hash_combine(_ptr(out), _ptr(np.ascontiguousarray(b, np.uint64)), len(out))
        return out
    x = a ^ (b + np.uint64(0x9E3779B97F4A7C15) + (a << np.uint64(6)) + (a >> np.uint64(2)))
    x = x + np.uint64(0x9E3779B97F4A7C15)
    x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return x ^ (x >> np.uint64(31))


def partition_indices(hashes: np.ndarray, nparts: int):
    """-> (indices int64[n] grouped by partition, counts int64[nparts]).

    indices[:counts[0]] are partition 0's rows (stable order), etc."""
    if nparts > MAX_PARTITIONS:
        raise ValueError(f"nparts {nparts} exceeds {MAX_PARTITIONS}")
    hashes = np.ascontiguousarray(hashes, np.uint64)
    n = len(hashes)
    lib = get_lib()
    if lib is not None:
        part_of = np.empty(n, np.int32)
        counts = np.empty(nparts, np.int64)
        lib.partition_assign(_ptr(hashes), n, nparts, _ptr(part_of), _ptr(counts))
        out = np.empty(n, np.int64)
        lib.partition_gather(_ptr(part_of), n, nparts, _ptr(counts), _ptr(out))
        return out, counts
    part_of = (hashes % np.uint64(nparts)).astype(np.int64)
    counts = np.bincount(part_of, minlength=nparts).astype(np.int64)
    return np.argsort(part_of, kind="stable").astype(np.int64), counts
