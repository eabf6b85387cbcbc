"""Native (C++) host runtime — reference parity for Horovod's native layer.

SURVEY.md §3b: the reference's heavy machinery is C++ (coordinator, fusion
buffer, NCCL/MPI glue).  On TPU the device side of that is XLA's job; the
host-side pieces that still benefit from native code live here:

  * :func:`gather_rows` — multi-threaded, GIL-released batch assembly for
    the input pipeline (ShardedLoader's per-step host work).
  * :func:`crc32c` — checkpoint integrity checksums (same polynomial GCS
    uses for object checksums).

The library builds lazily from ``src/tpuframe_native.cc`` with g++ (see
``build.py``) and every consumer degrades gracefully to a pure-Python path
when the toolchain or binary is unavailable — capability, not a hard dep.
"""

from __future__ import annotations

import ctypes
import os
import threading

import numpy as np

_LOCK = threading.Lock()
_LIB: ctypes.CDLL | None = None
_LOAD_FAILED = False
_LOAD_ERROR: str | None = None


def _load() -> ctypes.CDLL | None:
    global _LIB, _LOAD_FAILED, _LOAD_ERROR
    if _LIB is not None or _LOAD_FAILED:
        return _LIB
    with _LOCK:
        if _LIB is not None or _LOAD_FAILED:
            return _LIB
        try:
            from tpuframe.native.build import build

            path = build()
            lib = ctypes.CDLL(path)
            lib.tf_gather_rows.argtypes = [
                ctypes.c_char_p, ctypes.POINTER(ctypes.c_int64),
                ctypes.c_int64, ctypes.c_int64, ctypes.c_char_p,
                ctypes.c_int32]
            lib.tf_gather_rows.restype = None
            lib.tf_crc32c.argtypes = [ctypes.c_char_p, ctypes.c_int64,
                                      ctypes.c_uint32]
            lib.tf_crc32c.restype = ctypes.c_uint32
            _LIB = lib
        except Exception as e:  # noqa: BLE001 — any failure → Python fallback
            _LOAD_FAILED = True
            _LOAD_ERROR = f"{type(e).__name__}: {e}"
    return _LIB


def available() -> bool:
    return _load() is not None


def load_error() -> str | None:
    """Why the library is unavailable (build or load failure), else None —
    the fallback is silent, so whoever must not run on it asks here."""
    _load()
    return _LOAD_ERROR


def gather_rows(src: np.ndarray, indices: np.ndarray,
                out: np.ndarray | None = None,
                n_threads: int | None = None) -> np.ndarray:
    """``out[i] = src[indices[i]]`` for row-major ``src``; multi-threaded
    native copy with the GIL released, numpy fancy-indexing fallback."""
    lib = _load()
    src = np.ascontiguousarray(src)
    idx = np.ascontiguousarray(indices, np.int64)
    if idx.ndim != 1:
        raise ValueError("indices must be 1-D")
    if np.any(idx < 0) or (len(idx) and int(idx.max()) >= len(src)):
        raise IndexError("gather index out of range")
    if out is None:
        out = np.empty((len(idx), *src.shape[1:]), src.dtype)
    if lib is None:
        np.take(src, idx, axis=0, out=out)
        return out
    row_bytes = src.dtype.itemsize * int(np.prod(src.shape[1:], dtype=np.int64))
    if n_threads is None:
        n_threads = min(8, os.cpu_count() or 1)
    lib.tf_gather_rows(
        src.ctypes.data_as(ctypes.c_char_p),
        idx.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        len(idx), row_bytes, out.ctypes.data_as(ctypes.c_char_p),
        n_threads)
    return out


def crc32c(data: bytes | np.ndarray, seed: int = 0) -> int:
    """Castagnoli CRC-32 (native slicing-by-8, zlib-based fallback is NOT
    compatible — pure-Python fallback implements the same polynomial)."""
    if isinstance(data, np.ndarray):
        data = np.ascontiguousarray(data).tobytes()
    lib = _load()
    if lib is not None:
        return int(lib.tf_crc32c(data, len(data), seed))
    return _crc32c_py(data, seed)


_PY_TABLE: list[int] | None = None


def _crc32c_py(data: bytes, seed: int) -> int:
    global _PY_TABLE
    if _PY_TABLE is None:
        table = []
        for i in range(256):
            crc = i
            for _ in range(8):
                crc = (crc >> 1) ^ (0x82F63B78 if crc & 1 else 0)
            table.append(crc)
        _PY_TABLE = table
    crc = ~seed & 0xFFFFFFFF
    for b in data:
        crc = _PY_TABLE[(crc ^ b) & 0xFF] ^ (crc >> 8)
    return (~crc) & 0xFFFFFFFF
