"""ctypes bindings for the tpu-table native host runtime.

Builds native/tputable.cpp with g++ on first import (content-hashed so
rebuilds happen only when the source changes) and exposes:

- lz4_compress / lz4_decompress — LZ4 block codec (shuffle/spill)
- columns_to_rows / rows_to_columns — fixed-width row<->columnar
  conversion (CudfUnsafeRow / RowConversion role)
- HostMemoryPool — aligned slab allocator with alloc-failure signaling
  (HostAlloc / PinnedMemoryPool role)
- direct_write / direct_read — O_DIRECT spill-file transfer (the
  GDS-spill role: bulk spills bypass the page cache; buffered fallback
  when the filesystem refuses O_DIRECT)

SURVEY §2.9: these are the framework's native equivalents of the
reference's external C++/CUDA artifacts.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import Optional

import numpy as np

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_SRCS = [os.path.join(_REPO_ROOT, "native", "tputable.cpp"),
         os.path.join(_REPO_ROOT, "native", "parquet_decode.cpp"),
         os.path.join(_REPO_ROOT, "native", "orc_decode.cpp")]
_BUILD_DIR = os.path.join(_REPO_ROOT, "native", "build")

_LIB = None
_LIB_LOCK = threading.Lock()


def _zstd_link_args():
    """Link zstd however this box provides it: ``-lzstd`` when the dev
    package's unversioned symlink exists, else the runtime soname by
    path (images often ship libzstd.so.1 without zstd-dev; the two
    simple-API symbols we call are ABI-stable)."""
    try:
        out = subprocess.run(["ldconfig", "-p"], capture_output=True,
                             text=True).stdout
    except Exception:
        return ["-lzstd"]
    soname = None
    for line in out.splitlines():
        if "libzstd.so" not in line or "=>" not in line:
            continue
        path = line.split("=>")[-1].strip()
        if path.endswith("libzstd.so"):
            return ["-lzstd"]
        soname = soname or path
    return [soname] if soname else ["-lzstd"]


def _build_lib() -> str:
    h = hashlib.sha256()
    for src in _SRCS:
        with open(src, "rb") as f:
            h.update(f.read())
    digest = h.hexdigest()[:16]
    os.makedirs(_BUILD_DIR, exist_ok=True)
    so = os.path.join(_BUILD_DIR, f"libtputable-{digest}.so")
    if not os.path.exists(so):
        tmp = so + ".tmp"
        subprocess.run(
            ["g++", "-O3", "-shared", "-fPIC", "-std=c++17", "-o", tmp]
            + _SRCS + ["-lz"] + _zstd_link_args(),
            check=True, capture_output=True)
        os.replace(tmp, so)
    return so


#: glibc's mallopt parameters (malloc.h)
_M_TRIM_THRESHOLD, _M_TOP_PAD, _M_MMAP_THRESHOLD = -1, -2, -3
#: the largest mmap threshold glibc takes (half a thread arena's heap), and
#: that whole heap: a pad this large keeps a thread arena's emptied heap mapped
_MMAP_THRESHOLD_MAX, _ARENA_HEAP = 32 << 20, 64 << 20


def _retain_host_heap() -> bool:
    """Keep the host heap the scan has grown, once per process; says
    whether the allocator took all three settings.

    A scan makes and frees a few hundred MB of host buffers a query
    (decoded column chunks, the coalesced batch, the padded upload), 2-8 MB
    each. Left alone, glibc serves those from fresh ``mmap``s or from heaps
    it trims and unmaps as they empty, and which of the two a process does
    follows from the arena its scan thread happened to get: one process
    page-faults its buffers in again every query (thousands of faults, the
    mapping lock held against every other thread) and the next does not.
    Fixed thresholds take that draw away: buffers up to 32 MB come from
    the heap, and the heap is never trimmed or unmapped, so a process holds
    its high-water mark of host memory, as a pinned pool would (HostAlloc /
    PinnedMemoryPool role). A libc without ``mallopt`` is left as it is."""
    try:
        mallopt = ctypes.CDLL(None).mallopt
    except (OSError, AttributeError):
        return False
    mallopt.argtypes, mallopt.restype = [ctypes.c_int, ctypes.c_int], ctypes.c_int
    # all three, whatever the first returns: any one of them ends the
    # thresholds' drift
    taken = [mallopt(_M_MMAP_THRESHOLD, _MMAP_THRESHOLD_MAX),
             mallopt(_M_TRIM_THRESHOLD, 2**31 - 1),
             mallopt(_M_TOP_PAD, _ARENA_HEAP)]
    return all(taken)


def _lib() -> ctypes.CDLL:
    global _LIB
    with _LIB_LOCK:
        if _LIB is None:
            lib = ctypes.CDLL(_build_lib())
            _retain_host_heap()
            u8p = ctypes.POINTER(ctypes.c_uint8)
            lib.slz4_max_compressed_size.restype = ctypes.c_int64
            lib.slz4_max_compressed_size.argtypes = [ctypes.c_int64]
            lib.slz4_compress.restype = ctypes.c_int64
            lib.slz4_compress.argtypes = [u8p, ctypes.c_int64, u8p,
                                          ctypes.c_int64]
            lib.slz4_decompress.restype = ctypes.c_int64
            lib.slz4_decompress.argtypes = [u8p, ctypes.c_int64, u8p,
                                            ctypes.c_int64]
            lib.hostpool_create.restype = ctypes.c_void_p
            lib.hostpool_create.argtypes = [ctypes.c_int64]
            lib.hostpool_destroy.argtypes = [ctypes.c_void_p]
            lib.hostpool_alloc.restype = ctypes.c_void_p
            lib.hostpool_alloc.argtypes = [ctypes.c_void_p, ctypes.c_int64]
            lib.hostpool_free.restype = ctypes.c_int
            lib.hostpool_free.argtypes = [ctypes.c_void_p, ctypes.c_void_p]
            lib.hostpool_stats.argtypes = [ctypes.c_void_p,
                                           ctypes.POINTER(ctypes.c_int64)]
            u8pp = ctypes.POINTER(ctypes.POINTER(ctypes.c_uint8))
            i32p = ctypes.POINTER(ctypes.c_int32)
            lib.columns_to_rows.restype = None
            lib.columns_to_rows.argtypes = [
                u8pp, u8pp, i32p, i32p, ctypes.c_int32, ctypes.c_int64,
                u8p, ctypes.c_int64]
            lib.rows_to_columns.restype = None
            lib.rows_to_columns.argtypes = [
                u8p, ctypes.c_int64, ctypes.c_int64, i32p, i32p,
                ctypes.c_int32, u8pp, u8pp]
            # int64 size/return: default c_int truncation silently broke
            # >=2GiB O_DIRECT spills (the size compare always failed and
            # fell back to buffered npz)
            lib.direct_write_file.restype = ctypes.c_int64
            lib.direct_write_file.argtypes = [ctypes.c_char_p, u8p,
                                              ctypes.c_int64]
            lib.direct_read_file.restype = ctypes.c_int64
            lib.direct_read_file.argtypes = [ctypes.c_char_p, u8p,
                                             ctypes.c_int64]
            lib.parquet_decode_chunk.restype = ctypes.c_int64
            lib.parquet_decode_chunk.argtypes = [
                u8p, ctypes.c_int64, ctypes.c_int32, ctypes.c_int32,
                ctypes.c_int64, ctypes.c_int32, u8p, ctypes.c_int64,
                u8p, u8p, ctypes.c_int64]
            lib.orc_deframe.restype = ctypes.c_int64
            lib.orc_deframe.argtypes = [u8p, ctypes.c_int64,
                                        ctypes.c_int32, u8p,
                                        ctypes.c_int64]
            lib.orc_bool_rle.restype = ctypes.c_int64
            lib.orc_bool_rle.argtypes = [u8p, ctypes.c_int64, u8p,
                                         ctypes.c_int64]
            lib.orc_rlev2.restype = ctypes.c_int64
            lib.orc_rlev2.argtypes = [
                u8p, ctypes.c_int64, ctypes.c_int32,
                ctypes.POINTER(ctypes.c_int64), ctypes.c_int64]
            lib.orc_decimal64.restype = ctypes.c_int64
            lib.orc_decimal64.argtypes = [
                u8p, ctypes.c_int64,
                ctypes.POINTER(ctypes.c_int64), ctypes.c_int64]
            lib.parquet_decode_chunk_binary.restype = ctypes.c_int64
            lib.parquet_decode_chunk_binary.argtypes = [
                u8p, ctypes.c_int64, ctypes.c_int32, ctypes.c_int64,
                ctypes.c_int32, i32p, u8p, ctypes.c_int64, u8p, u8p,
                ctypes.c_int64]
            _LIB = lib
        return _LIB


def _u8ptr(arr: np.ndarray):
    return arr.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))


def lz4_compress(data: bytes) -> bytes:
    lib = _lib()
    src = np.frombuffer(data, np.uint8)
    cap = int(lib.slz4_max_compressed_size(len(src)))
    dst = np.empty(cap, np.uint8)
    n = int(lib.slz4_compress(_u8ptr(src), len(src), _u8ptr(dst), cap))
    if n < 0:
        raise RuntimeError("lz4 compression overflow")
    return dst[:n].tobytes()


def lz4_decompress(data: bytes, decompressed_size: int) -> bytes:
    lib = _lib()
    src = np.frombuffer(data, np.uint8)
    dst = np.empty(decompressed_size, np.uint8)
    n = int(lib.slz4_decompress(_u8ptr(src), len(src), _u8ptr(dst),
                                decompressed_size))
    if n != decompressed_size:
        raise RuntimeError(
            f"lz4 decompression produced {n}, expected "
            f"{decompressed_size}")
    return dst.tobytes()


def columns_to_rows(col_data, col_valid, field_sizes) -> np.ndarray:
    """Pack columnar buffers into fixed-width rows.

    col_data: list of contiguous np arrays (one per column)
    col_valid: list of uint8/bool arrays
    Returns (rows bytes ndarray, row_stride, field_offsets).
    """
    lib = _lib()
    n_cols = len(col_data)
    n_rows = len(col_data[0]) if n_cols else 0
    null_bytes = (n_cols + 7) // 8
    # 8-byte aligned fields after the null bitset (CudfUnsafeRow layout)
    offsets = []
    pos = (null_bytes + 7) // 8 * 8
    for s in field_sizes:
        pos = (pos + s - 1) // s * s  # natural alignment
        offsets.append(pos)
        pos += s
    stride = (pos + 7) // 8 * 8
    rows = np.zeros(n_rows * stride, np.uint8)
    data_arrs = [np.ascontiguousarray(a).view(np.uint8).reshape(-1)
                 for a in col_data]
    valid_arrs = [np.ascontiguousarray(v, dtype=np.uint8)
                  for v in col_valid]
    DataPtrs = ctypes.POINTER(ctypes.c_uint8) * n_cols
    dp = DataPtrs(*[_u8ptr(a) for a in data_arrs])
    vp = DataPtrs(*[_u8ptr(v) for v in valid_arrs])
    fs = (ctypes.c_int32 * n_cols)(*field_sizes)
    fo = (ctypes.c_int32 * n_cols)(*offsets)
    lib.columns_to_rows(dp, vp, fs, fo, n_cols, n_rows, _u8ptr(rows),
                        stride)
    return rows, stride, offsets


def rows_to_columns(rows: np.ndarray, stride: int, n_rows: int,
                    field_sizes, field_offsets, np_dtypes):
    """Unpack fixed-width rows into columnar (data, valid) pairs."""
    lib = _lib()
    n_cols = len(field_sizes)
    outs = [np.zeros(n_rows, np.dtype(d)) for d in np_dtypes]
    valids = [np.zeros(n_rows, np.uint8) for _ in range(n_cols)]
    DataPtrs = ctypes.POINTER(ctypes.c_uint8) * n_cols
    dp = DataPtrs(*[_u8ptr(a.view(np.uint8).reshape(-1)) for a in outs])
    vp = DataPtrs(*[_u8ptr(v) for v in valids])
    fs = (ctypes.c_int32 * n_cols)(*field_sizes)
    fo = (ctypes.c_int32 * n_cols)(*field_offsets)
    lib.rows_to_columns(_u8ptr(rows), stride, n_rows, fs, fo, n_cols,
                        dp, vp)
    return outs, [v.astype(bool) for v in valids]


class HostMemoryPool:
    """Aligned slab allocator; alloc returns None when exhausted so the
    caller can spill-and-retry (DeviceMemoryEventHandler pattern on the
    host side)."""

    def __init__(self, size: int):
        self._lib = _lib()
        self._pool = self._lib.hostpool_create(size)
        if not self._pool:
            raise MemoryError(f"hostpool_create({size})")
        self.size = size

    def alloc(self, size: int) -> Optional[int]:
        p = self._lib.hostpool_alloc(self._pool, size)
        return p or None

    def free(self, ptr: int) -> None:
        if self._lib.hostpool_free(self._pool, ptr) != 0:
            raise ValueError("hostpool_free: unknown pointer")

    def stats(self) -> dict:
        out = (ctypes.c_int64 * 4)()
        self._lib.hostpool_stats(self._pool, out)
        return {"in_use": out[0], "peak": out[1],
                "alloc_count": out[2], "fail_count": out[3]}

    def close(self) -> None:
        if self._pool:
            self._lib.hostpool_destroy(self._pool)
            self._pool = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


def direct_write(path: str, ptr: int, size: int) -> bool:
    """Write ``size`` bytes at address ``ptr`` to ``path`` with
    O_DIRECT when the filesystem allows (GDS-spill role)."""
    lib = _lib()
    buf = ctypes.cast(ptr, ctypes.POINTER(ctypes.c_uint8))
    return lib.direct_write_file(path.encode(), buf, size) == size


def direct_read(path: str, ptr: int, size: int) -> bool:
    lib = _lib()
    buf = ctypes.cast(ptr, ctypes.POINTER(ctypes.c_uint8))
    return lib.direct_read_file(path.encode(), buf, size) == size


def parquet_decode_chunk(chunk: bytes, codec: int, phys_type: int,
                         num_rows: int, max_def_level: int,
                         values: np.ndarray, validity: np.ndarray,
                         scratch: np.ndarray) -> int:
    """Decode one parquet column chunk's pages into ``values`` (dense
    fixed-width rows, zeros under nulls) + ``validity`` (u8/row).
    Returns rows decoded; negative = malformed(-1) / unsupported(-2) /
    buffer too small(-3) — the caller falls back to pyarrow."""
    lib = _lib()
    buf = np.frombuffer(chunk, dtype=np.uint8)
    return lib.parquet_decode_chunk(
        _u8ptr(buf), len(chunk), codec, phys_type, num_rows,
        max_def_level, _u8ptr(values), values.nbytes,
        _u8ptr(validity), _u8ptr(scratch), scratch.nbytes)


def parquet_decode_chunk_binary(chunk: bytes, codec: int, num_rows: int,
                                max_def_level: int, offsets: np.ndarray,
                                out_bytes: np.ndarray,
                                validity: np.ndarray,
                                scratch: np.ndarray) -> int:
    """Decode one BYTE_ARRAY column chunk into offsets[num_rows+1]
    (int32) + concatenated bytes. PLAIN / dictionary /
    DELTA_LENGTH_BYTE_ARRAY / DELTA_BYTE_ARRAY. Returns rows decoded;
    -3 also signals out_bytes too small (caller may retry bigger)."""
    import ctypes as _ct
    lib = _lib()
    buf = np.frombuffer(chunk, dtype=np.uint8)
    return int(lib.parquet_decode_chunk_binary(
        _u8ptr(buf), len(chunk), codec, num_rows, max_def_level,
        offsets.ctypes.data_as(_ct.POINTER(_ct.c_int32)),
        _u8ptr(out_bytes), out_bytes.nbytes, _u8ptr(validity),
        _u8ptr(scratch), scratch.nbytes))


def orc_decimal64(src: np.ndarray, out: np.ndarray, count: int) -> int:
    """ORC decimal DATA stream: zigzag unbounded varints -> int64
    unscaled values (precision <= 18)."""
    import ctypes as _ct
    lib = _lib()
    return int(lib.orc_decimal64(
        _u8ptr(src), len(src),
        out.ctypes.data_as(_ct.POINTER(_ct.c_int64)), count))


def load() -> None:
    """Build (once, from the committed native/*.cpp into native/build/)
    and load the native library. Raises what g++ or the loader raised —
    for callers to whom a broken build is a failure, not a reason to
    take the host path."""
    _lib()


def native_available() -> bool:
    try:
        _lib()
        return True
    except Exception:
        return False


def orc_deframe(src: np.ndarray, codec: int, dst: np.ndarray) -> int:
    """ORC compression deframing (3-byte chunk headers over
    zlib/snappy/zstd); returns decompressed length or negative error."""
    lib = _lib()
    return int(lib.orc_deframe(_u8ptr(src), len(src), codec,
                               _u8ptr(dst), len(dst)))


def orc_bool_rle(src: np.ndarray, out_valid: np.ndarray,
                 count: int) -> int:
    """PRESENT stream decode: byte-RLE bit bytes -> one u8 per value."""
    lib = _lib()
    return int(lib.orc_bool_rle(_u8ptr(src), len(src),
                                _u8ptr(out_valid), count))


def orc_rlev2(src: np.ndarray, is_signed: int, out: np.ndarray,
              count: int) -> int:
    """Integer RLEv2 decode into an int64 array."""
    import ctypes as _ct
    lib = _lib()
    return int(lib.orc_rlev2(
        _u8ptr(src), len(src), is_signed,
        out.ctypes.data_as(_ct.POINTER(_ct.c_int64)), count))
