"""ctypes bindings for the native companion library (native/srt_native.cpp).

The reference's JVM layer calls C++/CUDA through JNI (spark-rapids-jni
`Hash`/`CastStrings`, nvcomp codecs — SURVEY §2.9); here the host-side
native layer is a small C++ .so built on first use with g++ (no pybind11 in
the image, so the ABI is plain C + ctypes).  Every entry point has a numpy
fallback so the engine still works where a toolchain is unavailable —
``available()`` reports which path is active.
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
import threading
from typing import Optional, Tuple

import numpy as np

log = logging.getLogger("spark_rapids_tpu")

__all__ = ["available", "murmur3_int", "murmur3_long", "murmur3_utf8",
           "murmur3_fold", "normalize_float_bits", "pmod_partition",
           "xxhash64_long", "xxhash64_bytes", "compress", "decompress",
           "cast_string_to_long", "cast_string_to_double"]

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SRC = os.path.join(_REPO, "native", "srt_native.cpp")
_BUILD_DIR = os.path.join(_REPO, "native", "build")

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False


def _build() -> Optional[str]:
    """The library built from this source, named by the source's content
    hash: a copied tree keeps no mtimes, and a binary built from another
    version of the source must never be loaded."""
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    so = os.path.join(_BUILD_DIR, f"libsrt_native-{digest}.so")
    if os.path.exists(so):
        return so
    os.makedirs(_BUILD_DIR, exist_ok=True)
    # build under a private name, then rename: a concurrent process never
    # loads a half-written library
    tmp = f"{so}.{os.getpid()}.tmp"
    cmd = ["g++", "-O3", "-shared", "-fPIC", "-std=c++17",
           "-o", tmp, _SRC]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp, so)
        return so
    except Exception as e:
        log.warning("native build failed (%s); using numpy fallbacks", e)
        return None


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _tried
    with _lock:
        if _tried:
            return _lib
        _tried = True
        so = _build()
        if so is None:
            return None
        lib = ctypes.CDLL(so)
        i64p = ctypes.POINTER(ctypes.c_int64)
        i32p = ctypes.POINTER(ctypes.c_int32)
        u8p = ctypes.POINTER(ctypes.c_uint8)
        f64p = ctypes.POINTER(ctypes.c_double)
        lib.srt_murmur3_long.argtypes = [i64p, i32p, i32p, ctypes.c_int64]
        lib.srt_murmur3_utf8.argtypes = [u8p, i64p, i32p, i32p,
                                         ctypes.c_int64]
        lib.srt_pmod_partition.argtypes = [i32p, ctypes.c_int32, i32p,
                                           ctypes.c_int64]
        lib.srt_xxhash64_long.argtypes = [i64p, i64p, i64p, ctypes.c_int64]
        lib.srt_compress_bound.argtypes = [ctypes.c_int64]
        lib.srt_compress_bound.restype = ctypes.c_int64
        lib.srt_compress.argtypes = [u8p, ctypes.c_int64, u8p,
                                     ctypes.c_int64]
        lib.srt_compress.restype = ctypes.c_int64
        lib.srt_decompress.argtypes = [u8p, ctypes.c_int64, u8p,
                                       ctypes.c_int64]
        lib.srt_decompress.restype = ctypes.c_int64
        lib.srt_cast_string_to_long.argtypes = [u8p, i64p, i64p, u8p,
                                                ctypes.c_int64]
        lib.srt_cast_string_to_double.argtypes = [u8p, i64p, f64p, u8p,
                                                  ctypes.c_int64]
        _lib = lib
        return _lib


def available() -> bool:
    return _load() is not None


def _ptr(arr: np.ndarray, ctype):
    return arr.ctypes.data_as(ctypes.POINTER(ctype))


# ---------------------------------------------------------------------------------
# hashing (spark-rapids-jni Hash analog)
# ---------------------------------------------------------------------------------

def murmur3_long(vals: np.ndarray, seeds) -> np.ndarray:
    """Spark Murmur3Hash over int64 rows; ``seeds`` scalar or per-row."""
    vals = np.ascontiguousarray(vals, dtype=np.int64)
    n = len(vals)
    seeds = np.full(n, seeds, dtype=np.int32) if np.isscalar(seeds) \
        else np.ascontiguousarray(seeds, dtype=np.int32)
    lib = _load()
    out = np.empty(n, dtype=np.int32)
    if lib is not None:
        lib.srt_murmur3_long(_ptr(vals, ctypes.c_int64),
                             _ptr(seeds, ctypes.c_int32),
                             _ptr(out, ctypes.c_int32), n)
        return out
    u = vals.view(np.uint64)
    h = seeds.astype(np.uint32)
    h = _np_mix_h1(h, _np_mix_k1((u & 0xffffffff).astype(np.uint32)))
    h = _np_mix_h1(h, _np_mix_k1((u >> np.uint64(32)).astype(np.uint32)))
    return _np_fmix(h, 8).view(np.int32)


def murmur3_int(vals: np.ndarray, seeds) -> np.ndarray:
    """Spark Murmur3Hash over 4-byte values (int/short/byte/bool/date as
    int32); matches the device fold ``ops/hashing._hash_int32``."""
    vals = np.ascontiguousarray(vals, dtype=np.int32)
    n = len(vals)
    seeds = np.full(n, seeds, dtype=np.int32) if np.isscalar(seeds) \
        else np.ascontiguousarray(seeds, dtype=np.int32)
    h = _np_mix_h1(seeds.view(np.uint32), _np_mix_k1(vals.view(np.uint32)))
    return _np_fmix(h, 4).view(np.int32)


def murmur3_utf8(bytes_: np.ndarray, offsets: np.ndarray, seeds
                 ) -> np.ndarray:
    """Spark Murmur3Hash over utf8 strings in Arrow offsets+bytes layout."""
    bytes_ = np.ascontiguousarray(bytes_, dtype=np.uint8)
    offsets = np.ascontiguousarray(offsets, dtype=np.int64)
    n = len(offsets) - 1
    seeds = np.full(n, seeds, dtype=np.int32) if np.isscalar(seeds) \
        else np.ascontiguousarray(seeds, dtype=np.int32)
    lib = _load()
    out = np.empty(n, dtype=np.int32)
    if lib is not None:
        lib.srt_murmur3_utf8(_ptr(bytes_, ctypes.c_uint8),
                             _ptr(offsets, ctypes.c_int64),
                             _ptr(seeds, ctypes.c_int32),
                             _ptr(out, ctypes.c_int32), n)
        return out
    # python fallback (slow but correct)
    for i in range(n):
        p = bytes_[offsets[i]:offsets[i + 1]]
        h = np.uint32(seeds[i])
        nb = len(p) // 4
        for b in range(nb):
            k = np.frombuffer(p[b * 4:b * 4 + 4].tobytes(),
                              dtype="<u4")[0]
            h = _np_mix_h1(h, _np_mix_k1(k))
        for b in range(nb * 4, len(p)):
            sb = int(p[b]) - 256 if p[b] >= 128 else int(p[b])
            k = np.uint32(sb & 0xffffffff)
            h = _np_mix_h1(h, _np_mix_k1(k))
        out[i] = np.int32(_np_fmix(h, len(p)))
    return out


def _np_mix_k1(k1):
    with np.errstate(over="ignore"):
        k1 = (k1 * np.uint32(0xcc9e2d51)).astype(np.uint32)
        k1 = (k1 << np.uint32(15)) | (k1 >> np.uint32(17))
        return (k1 * np.uint32(0x1b873593)).astype(np.uint32)


def _np_mix_h1(h1, k1):
    with np.errstate(over="ignore"):
        h1 = (h1 ^ k1).astype(np.uint32)
        h1 = (h1 << np.uint32(13)) | (h1 >> np.uint32(19))
        return (h1 * np.uint32(5) + np.uint32(0xe6546b64)).astype(np.uint32)


def _np_fmix(h1, length):
    with np.errstate(over="ignore"):
        h1 = (h1 ^ np.uint32(length)).astype(np.uint32)
        h1 ^= h1 >> np.uint32(16)
        h1 = (h1 * np.uint32(0x85ebca6b)).astype(np.uint32)
        h1 ^= h1 >> np.uint32(13)
        h1 = (h1 * np.uint32(0xc2b2ae35)).astype(np.uint32)
        h1 ^= h1 >> np.uint32(16)
        return h1


def normalize_float_bits(vals: np.ndarray) -> np.ndarray:
    """-0.0 → +0.0 and NaN → canonical NaN, then the raw bit pattern —
    the ONE host definition matching the device kernel
    (ops/hashing._normalize_float_bits); shared by hash expressions and
    DCN partition ids so they cannot diverge."""
    v = vals.copy()
    v[v == 0.0] = 0.0
    v[np.isnan(v)] = np.nan
    return v.view(np.int32 if v.dtype == np.float32 else np.int64)


def murmur3_fold(vals: np.ndarray, dt, seeds) -> np.ndarray:
    """Fold one non-string column (numpy physical values + logical dtype)
    into running murmur3 hashes — the host twin of ops/hashing.hash_value."""
    if dt.is_floating:
        vals = normalize_float_bits(
            np.ascontiguousarray(vals, dtype=dt.numpy_dtype))
    if vals.dtype in (np.dtype(np.int64), np.dtype(np.uint64)):
        return murmur3_long(vals.view(np.int64), seeds)
    return murmur3_int(vals.astype(np.int32), seeds)


def pmod_partition(hashes: np.ndarray, num_parts: int) -> np.ndarray:
    hashes = np.ascontiguousarray(hashes, dtype=np.int32)
    lib = _load()
    out = np.empty(len(hashes), dtype=np.int32)
    if lib is not None:
        lib.srt_pmod_partition(_ptr(hashes, ctypes.c_int32), num_parts,
                               _ptr(out, ctypes.c_int32), len(hashes))
        return out
    m = hashes.astype(np.int64) % num_parts
    return np.where(m < 0, m + num_parts, m).astype(np.int32)


def xxhash64_long(vals: np.ndarray, seed: int = 42) -> np.ndarray:
    vals = np.ascontiguousarray(vals, dtype=np.int64)
    n = len(vals)
    seeds = np.full(n, seed, dtype=np.int64)
    lib = _load()
    out = np.empty(n, dtype=np.int64)
    if lib is not None:
        lib.srt_xxhash64_long(_ptr(vals, ctypes.c_int64),
                              _ptr(seeds, ctypes.c_int64),
                              _ptr(out, ctypes.c_int64), n)
        return out
    P1, P2, P3 = (np.uint64(0x9E3779B185EBCA87), np.uint64(0xC2B2AE3D27D4EB4F),
                  np.uint64(0x165667B19E3779F9))
    P4, P5 = np.uint64(0x85EBCA77C2B2AE63), np.uint64(0x27D4EB2F165667C5)
    with np.errstate(over="ignore"):
        h = seeds.view(np.uint64) + P5 + np.uint64(8)
        k1 = vals.view(np.uint64) * P2
        k1 = (k1 << np.uint64(31)) | (k1 >> np.uint64(33))
        k1 *= P1
        h ^= k1
        h = ((h << np.uint64(27)) | (h >> np.uint64(37))) * P1 + P4
        h ^= h >> np.uint64(33)
        h *= P2
        h ^= h >> np.uint64(29)
        h *= P3
        h ^= h >> np.uint64(32)
    return h.view(np.int64)


_XXP = (0x9E3779B185EBCA87, 0xC2B2AE3D27D4EB4F, 0x165667B19E3779F9,
        0x85EBCA77C2B2AE63, 0x27D4EB2F165667C5)
_M64 = (1 << 64) - 1


def xxhash64_bytes(data: bytes, seed: int = 42) -> int:
    """Canonical XXH64 over arbitrary bytes (Spark XxHash64 on utf8
    strings/binary).  Pure-python ints — the CPU fallback path for string
    hashing; verified against python-xxhash golden values in the tests."""
    P1, P2, P3, P4, P5 = _XXP

    def rotl(x, r):
        return ((x << r) | (x >> (64 - r))) & _M64

    def rnd(acc, inp):
        return (rotl((acc + inp * P2) & _M64, 31) * P1) & _M64

    n = len(data)
    pos = 0
    if n >= 32:
        v1 = (seed + P1 + P2) & _M64
        v2 = (seed + P2) & _M64
        v3 = seed & _M64
        v4 = (seed - P1) & _M64
        while pos + 32 <= n:
            v1 = rnd(v1, int.from_bytes(data[pos:pos + 8], "little"))
            v2 = rnd(v2, int.from_bytes(data[pos + 8:pos + 16], "little"))
            v3 = rnd(v3, int.from_bytes(data[pos + 16:pos + 24], "little"))
            v4 = rnd(v4, int.from_bytes(data[pos + 24:pos + 32], "little"))
            pos += 32
        h = (rotl(v1, 1) + rotl(v2, 7) + rotl(v3, 12) + rotl(v4, 18)) & _M64
        for v in (v1, v2, v3, v4):
            h = ((h ^ rnd(0, v)) * P1 + P4) & _M64
    else:
        h = (seed + P5) & _M64
    h = (h + n) & _M64
    while pos + 8 <= n:
        k1 = rnd(0, int.from_bytes(data[pos:pos + 8], "little"))
        h = (rotl(h ^ k1, 27) * P1 + P4) & _M64
        pos += 8
    if pos + 4 <= n:
        h = (rotl(h ^ (int.from_bytes(data[pos:pos + 4], "little") * P1)
                  & _M64, 23) * P2 + P3) & _M64
        pos += 4
    while pos < n:
        h = (rotl(h ^ (data[pos] * P5) & _M64, 11) * P1) & _M64
        pos += 1
    h = ((h ^ (h >> 33)) * P2) & _M64
    h = ((h ^ (h >> 29)) * P3) & _M64
    return h ^ (h >> 32)


# ---------------------------------------------------------------------------------
# spill/shuffle block codec (nvcomp analog)
# ---------------------------------------------------------------------------------

def compress(data: bytes) -> Optional[bytes]:
    """Compress a spill/shuffle payload; None when native is unavailable
    (callers then store raw)."""
    lib = _load()
    if lib is None:
        return None
    src = np.frombuffer(data, dtype=np.uint8)
    cap = int(lib.srt_compress_bound(len(src)))
    dst = np.empty(cap, dtype=np.uint8)
    k = int(lib.srt_compress(_ptr(src, ctypes.c_uint8), len(src),
                             _ptr(dst, ctypes.c_uint8), cap))
    if k < 0:
        return None
    return dst[:k].tobytes()


def decompress(data: bytes, raw_len: int) -> bytes:
    lib = _load()
    if lib is None:
        raise RuntimeError("native codec unavailable for decompress")
    src = np.frombuffer(data, dtype=np.uint8)
    dst = np.empty(raw_len, dtype=np.uint8)
    k = int(lib.srt_decompress(_ptr(src, ctypes.c_uint8), len(src),
                               _ptr(dst, ctypes.c_uint8), raw_len))
    if k != raw_len:
        raise ValueError(f"corrupt compressed block ({k} != {raw_len})")
    return dst.tobytes()


# ---------------------------------------------------------------------------------
# string casts (CastStrings analog)
# ---------------------------------------------------------------------------------

def cast_string_to_long(bytes_: np.ndarray, offsets: np.ndarray
                        ) -> Tuple[np.ndarray, np.ndarray]:
    """Spark-exact string→long: trim, invalid/overflow → null.
    Returns (values int64, valid bool)."""
    bytes_ = np.ascontiguousarray(bytes_, dtype=np.uint8)
    offsets = np.ascontiguousarray(offsets, dtype=np.int64)
    n = len(offsets) - 1
    lib = _load()
    out = np.empty(n, dtype=np.int64)
    valid = np.empty(n, dtype=np.uint8)
    if lib is not None:
        lib.srt_cast_string_to_long(_ptr(bytes_, ctypes.c_uint8),
                                    _ptr(offsets, ctypes.c_int64),
                                    _ptr(out, ctypes.c_int64),
                                    _ptr(valid, ctypes.c_uint8), n)
        return out, valid.astype(bool)
    for i in range(n):
        s = bytes_[offsets[i]:offsets[i + 1]].tobytes().decode(
            "utf-8", "replace").strip()
        try:
            out[i] = int(s)
            valid[i] = 1
        except ValueError:
            out[i] = 0
            valid[i] = 0
    return out, valid.astype(bool)


def cast_string_to_double(bytes_: np.ndarray, offsets: np.ndarray
                          ) -> Tuple[np.ndarray, np.ndarray]:
    bytes_ = np.ascontiguousarray(bytes_, dtype=np.uint8)
    offsets = np.ascontiguousarray(offsets, dtype=np.int64)
    n = len(offsets) - 1
    lib = _load()
    out = np.empty(n, dtype=np.float64)
    valid = np.empty(n, dtype=np.uint8)
    if lib is not None:
        lib.srt_cast_string_to_double(_ptr(bytes_, ctypes.c_uint8),
                                      _ptr(offsets, ctypes.c_int64),
                                      _ptr(out, ctypes.c_double),
                                      _ptr(valid, ctypes.c_uint8), n)
        return out, valid.astype(bool)
    for i in range(n):
        s = bytes_[offsets[i]:offsets[i + 1]].tobytes().decode(
            "utf-8", "replace").strip()
        try:
            out[i] = float(s)
            valid[i] = 1
        except ValueError:
            out[i] = 0.0
            valid[i] = 0
    return out, valid.astype(bool)
