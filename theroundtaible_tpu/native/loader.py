"""ctypes binding for the native runtime library (native/rt_native.cc).

Python parses the safetensors JSON header (bytes, not gigabytes); the C++
side mmaps the payload and does the multithreaded dtype conversion into
caller-owned numpy buffers. Everything degrades cleanly: when the library
is missing and can't be built, read_safetensors returns None and callers
fall back to the pure-Python `safetensors` package, and lcp falls back to
a Python loop.

The library self-builds on first use when g++ is available (a single
translation unit, ~1s) — same command as `make -C native`.
"""

from __future__ import annotations

import ctypes
import json
import struct
import subprocess
import threading
from pathlib import Path
from typing import Optional

import numpy as np

_PKG_DIR = Path(__file__).parent
_SO_PATH = _PKG_DIR / "librt_native.so"
_SRC_PATH = _PKG_DIR.parent.parent / "native" / "rt_native.cc"

_lib = None
_lib_tried = False
_lib_origin: Optional[str] = None   # "built" | "loaded" once resolved
_lock = threading.Lock()

# Must match DType in rt_native.cc.
_DTYPES = {"F32": 0, "F16": 1, "BF16": 2, "F64": 3, "I64": 4, "I32": 5,
           "U8": 6, "I8": 7}


class _TensorJob(ctypes.Structure):
    _fields_ = [
        ("src_offset", ctypes.c_uint64),
        ("n_elems", ctypes.c_uint64),
        ("src_dtype", ctypes.c_int32),
        ("pad", ctypes.c_int32),
        ("dst", ctypes.c_void_p),
    ]


def _build() -> bool:
    if not _SRC_PATH.exists():
        return False
    # build to a temp path + atomic rename: another process racing this
    # build must never dlopen a half-written .so
    import os
    tmp = _SO_PATH.with_suffix(f".tmp{os.getpid()}.so")
    try:
        subprocess.run(
            ["g++", "-O3", "-shared", "-fPIC", "-pthread", "-std=c++17",
             "-o", str(tmp), str(_SRC_PATH)],
            check=True, capture_output=True, timeout=120)
        os.replace(tmp, _SO_PATH)
        return _SO_PATH.exists()
    except (OSError, subprocess.SubprocessError):
        tmp.unlink(missing_ok=True)
        return False


def _stale() -> bool:
    """The .so is missing, or older than its source: the library is
    git-ignored, so a working tree can carry a build of an earlier
    rt_native.cc — what runs must come from the tracked source."""
    try:
        return (not _SO_PATH.exists()
                or (_SRC_PATH.exists() and _SRC_PATH.stat().st_mtime
                    > _SO_PATH.stat().st_mtime))
    except OSError:
        return True


def _get_lib(build: bool = True):
    global _lib, _lib_tried, _lib_origin
    with _lock:
        if _lib_tried:
            return _lib
        stale = _stale()
        if not build and stale:
            # latency-sensitive caller: load only if a fresh .so already
            # exists; never shell out to g++ and never latch a negative
            # result (a later load path may still build it)
            return None
        _lib_tried = True
        if stale and not _build():
            return None
        try:
            lib = ctypes.CDLL(str(_SO_PATH))
            lib.st_convert.restype = ctypes.c_int
            lib.st_convert.argtypes = [
                ctypes.c_char_p, ctypes.POINTER(_TensorJob),
                ctypes.c_int64, ctypes.c_int32]
            lib.rt_lcp.restype = ctypes.c_int64
            lib.rt_lcp.argtypes = [
                ctypes.POINTER(ctypes.c_int32), ctypes.c_int64,
                ctypes.POINTER(ctypes.c_int32), ctypes.c_int64]
            _lib = lib
            _lib_origin = "built" if stale else "loaded"
        except OSError:
            _lib = None
        return _lib


def native_available() -> bool:
    return _get_lib() is not None


def native_origin() -> Optional[str]:
    """How this process got the library: "built" (compiled from
    native/rt_native.cc just now — the .so was missing or older than
    the source), "loaded" (a fresh .so was already there), or None
    (unavailable, or not resolved yet — native_available() resolves)."""
    return _lib_origin


def iter_safetensors(path: str | Path, n_threads: int = 0):
    """Yield (name, float32 array) one tensor at a time.

    Streaming contract: peak host memory is ONE tensor's f32 copy, not the
    whole shard (a consolidated Mixtral shard would not fit doubled). The
    mmap inside st_convert is per-call but lazy, so per-tensor calls cost
    only the pages actually read; big tensors still fan out across
    converter threads. Yields nothing (empty iterator) when the library is
    unavailable — callers then fall back to the `safetensors` package.
    """
    lib = _get_lib()
    if lib is None:
        return
    path = Path(path)
    with open(path, "rb") as f:
        (header_len,) = struct.unpack("<Q", f.read(8))
        header = json.loads(f.read(header_len))
    payload_base = 8 + header_len

    _ELEM_SIZE = {"F32": 4, "F16": 2, "BF16": 2, "F64": 8, "I64": 8,
                  "I32": 4, "U8": 1, "I8": 1}
    for name, meta in header.items():
        if name == "__metadata__":
            continue
        dtype = meta["dtype"]
        if dtype not in _DTYPES:
            raise ValueError(f"unsupported safetensors dtype {dtype}")
        begin, end = meta["data_offsets"]
        out = np.empty(meta["shape"], np.float32)
        # a shape/offsets mismatch must fail loudly, not read the next
        # tensor's bytes as this one's tail
        if (begin < 0 or end < begin
                or end - begin != out.size * _ELEM_SIZE[dtype]):
            raise ValueError(
                f"tensor {name}: data_offsets {begin}:{end} disagree "
                f"with shape {meta['shape']} ({dtype})")
        job = (_TensorJob * 1)()
        job[0].src_offset = payload_base + begin
        job[0].n_elems = out.size
        job[0].src_dtype = _DTYPES[dtype]
        job[0].dst = out.ctypes.data
        rc = lib.st_convert(str(path).encode(), job, 1, n_threads)
        if rc != 0:
            raise OSError(f"st_convert failed ({rc}) on {path}")
        yield name, out


def native_can_read(path: str | Path) -> bool:
    """Library built AND every tensor dtype in the file is convertible —
    checked up front so a stream never fails after partial yield."""
    if _get_lib() is None:
        return False
    try:
        with open(path, "rb") as f:
            (header_len,) = struct.unpack("<Q", f.read(8))
            header = json.loads(f.read(header_len))
        return all(meta.get("dtype") in _DTYPES
                   for name, meta in header.items()
                   if name != "__metadata__")
    except Exception:  # noqa: BLE001 — contract: malformed file → False,
        return False   # caller takes the safetensors-package fallback


def read_safetensors(path: str | Path,
                     n_threads: int = 0
                     ) -> Optional[dict[str, np.ndarray]]:
    """Read every tensor of a .safetensors file as float32 arrays at once.

    Convenience for small files/tests; checkpoint loading streams via
    iter_safetensors instead. Returns None when the native library is
    unavailable or a dtype is unsupported.
    """
    if _get_lib() is None:
        return None
    try:
        return dict(iter_safetensors(path, n_threads))
    except (ValueError, OSError) as e:
        if isinstance(e, FileNotFoundError):
            raise
        return None


def lcp(a: list[int], b: list[int]) -> int:
    """Longest common prefix of two token-id sequences (KV reuse).

    Serving hot path: consults only the already-loaded library handle (no
    lock, no filesystem stat, never the g++ self-build). Short inputs and
    early mismatches stay on the Python loop — it exits at the first
    differing token, cheaper than materializing int32 arrays."""
    n = min(len(a), len(b))
    if _lib is None or n < 1024 or a[0] != b[0]:
        i = 0
        while i < n and a[i] == b[i]:
            i += 1
        return i
    arr_a = np.asarray(a, np.int32)
    arr_b = np.asarray(b, np.int32)
    return int(_lib.rt_lcp(
        arr_a.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)), len(arr_a),
        arr_b.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)), len(arr_b)))
