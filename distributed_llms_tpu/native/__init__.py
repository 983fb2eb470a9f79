"""Native (C++) IO tier: build-on-first-use loader + ctypes bindings.

The reference is 100% Python (SURVEY §2 intro — no native components to
port), so this tier exists where native code actually pays on TPU hosts: the
checkpoint cold-load path.  ``read_segments`` fans per-tensor ``pread``s
over a C++ thread pool with CRC32 integrity computed in-pass; the pure-
Python fallback keeps every caller working when no compiler is available.

Build model: single-file ``g++ -O3 -shared`` compiled lazily into
``_cache/`` next to the source, under a name keyed on the source's
content — a binary built from any other source (or copied in from another
machine's tree, where git never saw it) is never the one loaded.  No
setuptools/pybind11 dependency.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
import zlib
from typing import Sequence

import numpy as np

from ..core.observability import get_logger

log = get_logger("native")

_SRC = os.path.join(os.path.dirname(__file__), "csrc", "dlt_io.cpp")
_CACHE = os.path.join(os.path.dirname(__file__), "_cache")

_lock = threading.Lock()
_lib: ctypes.CDLL | None = None
_tried = False


def _build() -> str | None:
    os.makedirs(_CACHE, exist_ok=True)
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    so_path = os.path.join(_CACHE, f"dlt_io.{digest}.so")
    if os.path.exists(so_path):
        return so_path
    # Per-process temp name: concurrent cold-start builds (e.g. the
    # process-isolated local sim spawning N workers) must not interleave
    # writes; os.replace makes the final install atomic either way.
    tmp = f"{so_path}.{os.getpid()}.tmp"
    cmd = [
        "g++", "-O3", "-shared", "-fPIC", "-std=c++17", "-pthread",
        _SRC, "-o", tmp,
    ]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp, so_path)
        return so_path
    except (OSError, subprocess.SubprocessError) as e:
        detail = getattr(e, "stderr", b"") or b""
        log.warning("native build failed (%s); using Python IO fallback: %s",
                    e, detail.decode(errors="replace")[:500])
        return None
    finally:
        if os.path.exists(tmp):
            try:
                os.unlink(tmp)
            except OSError:
                pass


def get_lib() -> ctypes.CDLL | None:
    """The compiled library, building it on first use; None if unavailable."""
    global _lib, _tried
    with _lock:
        if _tried:
            return _lib
        _tried = True
        so = _build()
        if so is None:
            return None
        try:
            lib = ctypes.CDLL(so)
        except OSError as e:
            log.warning("loading %s failed (%s); using Python IO fallback", so, e)
            return None
        lib.dlt_crc32.restype = ctypes.c_uint32
        lib.dlt_crc32.argtypes = [
            ctypes.c_char_p, ctypes.c_uint64, ctypes.c_uint32,
        ]
        lib.dlt_read_segments.restype = ctypes.c_int64
        lib.dlt_read_segments.argtypes = [
            ctypes.POINTER(ctypes.c_char_p),
            ctypes.POINTER(ctypes.c_uint64),
            ctypes.POINTER(ctypes.c_uint64),
            ctypes.POINTER(ctypes.c_void_p),
            ctypes.POINTER(ctypes.c_uint32),
            ctypes.c_int64,
            ctypes.c_int,
        ]
        _lib = lib
        return _lib


def available() -> bool:
    return get_lib() is not None


def read_segments(
    tasks: Sequence[tuple[str, int, int]],  # (path, offset, nbytes)
    threads: int = 8,
    with_crc: bool = True,
) -> tuple[list[np.ndarray], list[int] | None]:
    """Read byte segments (parallel native pread when available, Python
    fallback otherwise).  Returns (uint8 buffers, crc32s or None)."""
    lib = get_lib()
    if lib is None:
        return _read_segments_py(tasks, with_crc)
    n = len(tasks)
    bufs = [np.empty(nb, dtype=np.uint8) for _, _, nb in tasks]
    paths = (ctypes.c_char_p * n)(*(p.encode() for p, _, _ in tasks))
    offs = (ctypes.c_uint64 * n)(*(o for _, o, _ in tasks))
    sizes = (ctypes.c_uint64 * n)(*(nb for _, _, nb in tasks))
    ptrs = (ctypes.c_void_p * n)(*(b.ctypes.data for b in bufs))
    crcs = (ctypes.c_uint32 * n)() if with_crc else None
    rc = lib.dlt_read_segments(
        paths, offs, sizes, ptrs,
        crcs if with_crc else ctypes.cast(None, ctypes.POINTER(ctypes.c_uint32)),
        n, threads,
    )
    if rc != 0:
        i = int(rc) - 1
        raise IOError(f"native read failed for {tasks[i][0]} @ {tasks[i][1]}")
    return bufs, (list(crcs) if with_crc else None)


def _read_segments_py(
    tasks: Sequence[tuple[str, int, int]], with_crc: bool
) -> tuple[list[np.ndarray], list[int] | None]:
    bufs: list[np.ndarray] = []
    crcs: list[int] | None = [] if with_crc else None
    for path, off, nb in tasks:
        with open(path, "rb") as f:
            f.seek(off)
            data = f.read(nb)
        if len(data) != nb:
            raise IOError(f"short read from {path} @ {off} ({len(data)}/{nb})")
        buf = np.frombuffer(data, dtype=np.uint8)
        bufs.append(buf)
        if with_crc:
            crcs.append(zlib.crc32(data) & 0xFFFFFFFF)
    return bufs, crcs


def crc32(data: bytes | np.ndarray) -> int:
    """CRC32 via the native library when present (zlib fallback — identical
    polynomial, so stores written either way verify either way).  ndarray
    input is checksummed in place, no bytes copy."""
    lib = get_lib()
    if isinstance(data, np.ndarray):
        arr = np.ascontiguousarray(data)
        if lib is None:
            return zlib.crc32(arr.view(np.uint8).reshape(-1)) & 0xFFFFFFFF
        return int(
            lib.dlt_crc32(arr.ctypes.data_as(ctypes.c_char_p), arr.nbytes, 0)
        )
    if lib is None:
        return zlib.crc32(data) & 0xFFFFFFFF
    return int(lib.dlt_crc32(data, len(data), 0))
