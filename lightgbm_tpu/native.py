"""ctypes bindings for the native data-loading runtime.

The reference reaches its C++ core through ctypes (python-package/
lightgbm/basic.py:30-40 loading lib_lightgbm.so); we do the same for the
host-side ingest library (src/native/lgbm_native.cpp) that accelerates
text parsing and the value->bin encode.  The library is built on demand
with g++ (cached next to the package); every entry point has a pure
Python fallback, so the framework works without a toolchain.
"""

from __future__ import annotations

import ctypes
import fcntl
import os
import subprocess
from typing import List, Optional, Tuple

import numpy as np

from .analysis import lockcheck
from .log import Log

_LIB_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "lib")
_LIB_PATH = os.path.join(_LIB_DIR, "liblgbm_native.so")
_SRC = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "src", "native", "lgbm_native.cpp",
)
_lock = lockcheck.make_lock("native.load")
_lib: Optional[ctypes.CDLL] = None
_tried = False


def _fresh() -> bool:
    """Is the built library there and no older than its source?"""
    return os.path.exists(_LIB_PATH) and not (
        os.path.exists(_SRC)
        and os.path.getmtime(_SRC) > os.path.getmtime(_LIB_PATH))


def _build() -> bool:
    """Build the library where it is missing or stale.  Several processes
    (a test run's workers, in a fresh checkout) can find it missing at
    once: they take turns on a lock file beside it, each looks again
    once it holds the lock, and a build writes a file of its own and
    renames it into place, so no process loads a library another is
    still writing (one that did fell back to Python for its life)."""
    if not os.path.exists(_SRC):
        return False
    os.makedirs(_LIB_DIR, exist_ok=True)
    # a lock file, never written: closing it lets the next process in
    lock = os.open(os.path.join(_LIB_DIR, ".build.lock"),
                   os.O_CREAT | os.O_RDWR, 0o644)
    try:
        fcntl.flock(lock, fcntl.LOCK_EX)
        if _fresh():
            return True
        tmp = f"{_LIB_PATH}.{os.getpid()}.tmp"
        # the Makefile is the single source of truth for compile flags
        makefile_dir = os.path.dirname(_SRC)
        if os.path.exists(os.path.join(makefile_dir, "Makefile")):
            cmd = ["make", "-C", makefile_dir, "--always-make", f"OUT={tmp}"]
        else:
            cmd = ["g++", "-O3", "-std=c++17", "-Wall", "-fPIC", "-fopenmp",
                   "-shared", "-o", tmp, _SRC]
        try:
            proc = subprocess.run(cmd, capture_output=True, text=True,
                                  timeout=120)
        except (OSError, subprocess.TimeoutExpired):
            return False
        if proc.returncode != 0 or not os.path.exists(tmp):
            Log.warning(
                f"native build failed, using python IO: {proc.stderr[:500]}")
            return False
        os.replace(tmp, _LIB_PATH)
    finally:
        os.close(lock)
    return True


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        if os.environ.get("LIGHTGBM_TPU_NO_NATIVE"):
            return None
        if not _fresh() and not _build():
            return None
        try:
            lib = ctypes.CDLL(_LIB_PATH)
        except OSError as e:
            Log.warning(f"native lib load failed, using python IO: {e}")
            return None
        lib.lgbm_parse_delimited.restype = ctypes.c_int
        lib.lgbm_parse_delimited.argtypes = [
            ctypes.c_char_p, ctypes.c_int, ctypes.c_int,
            ctypes.POINTER(ctypes.POINTER(ctypes.c_double)),
            ctypes.POINTER(ctypes.c_long), ctypes.POINTER(ctypes.c_long),
        ]
        lib.lgbm_parse_libsvm.restype = ctypes.c_int
        lib.lgbm_parse_libsvm.argtypes = [
            ctypes.c_char_p, ctypes.c_int,
            ctypes.POINTER(ctypes.POINTER(ctypes.c_double)),
            ctypes.POINTER(ctypes.c_long), ctypes.POINTER(ctypes.c_long),
        ]
        lib.lgbm_detect_format.restype = ctypes.c_int
        lib.lgbm_detect_format.argtypes = [ctypes.c_char_p, ctypes.c_int]
        lib.lgbm_value_to_bin.restype = None
        lib.lgbm_value_to_bin.argtypes = [
            ctypes.POINTER(ctypes.c_double), ctypes.c_long, ctypes.c_long,
            ctypes.POINTER(ctypes.c_long), ctypes.c_long,
            ctypes.POINTER(ctypes.c_double), ctypes.POINTER(ctypes.c_long),
            ctypes.c_void_p, ctypes.c_int,
        ]
        lib.lgbm_free.restype = None
        lib.lgbm_free.argtypes = [ctypes.c_void_p]
        lib.lgbm_chunk_open.restype = ctypes.c_void_p
        lib.lgbm_chunk_open.argtypes = [
            ctypes.c_char_p, ctypes.c_int, ctypes.c_int,
            ctypes.POINTER(ctypes.c_long),
        ]
        lib.lgbm_chunk_next.restype = ctypes.c_long
        lib.lgbm_chunk_next.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_double), ctypes.c_long,
        ]
        lib.lgbm_chunk_close.restype = None
        lib.lgbm_chunk_close.argtypes = [ctypes.c_void_p]
        _lib = lib
        return _lib


def available() -> bool:
    return _load() is not None


def detect_format(path: str, skip_header: bool) -> Optional[str]:
    lib = _load()
    if lib is None:
        return None
    code = lib.lgbm_detect_format(path.encode(), int(skip_header))
    return {1: "csv", 2: "tsv", 3: "libsvm"}.get(code)


def parse_file(path: str, fmt: str, skip_header: bool) -> Optional[np.ndarray]:
    """Parse with the native runtime; None -> caller falls back to Python."""
    lib = _load()
    if lib is None:
        return None
    data_p = ctypes.POINTER(ctypes.c_double)()
    rows = ctypes.c_long()
    cols = ctypes.c_long()
    if fmt == "libsvm":
        rc = lib.lgbm_parse_libsvm(
            path.encode(), int(skip_header),
            ctypes.byref(data_p), ctypes.byref(rows), ctypes.byref(cols),
        )
    else:
        rc = lib.lgbm_parse_delimited(
            path.encode(), 1 if fmt == "csv" else 2, int(skip_header),
            ctypes.byref(data_p), ctypes.byref(rows), ctypes.byref(cols),
        )
    if rc != 0:
        return None
    n, f = rows.value, cols.value
    try:
        out = np.ctypeslib.as_array(data_p, shape=(n, f)).copy()
    finally:
        lib.lgbm_free(data_p)
    return out


def parse_file_chunks(path: str, fmt: str, skip_header: bool,
                      chunk_rows: int):
    """Streaming chunk parse (the native half of two-round loading,
    text_reader.h:144-288 semantics).  Yields row-major float64 chunks.
    Returns None when unavailable so the caller uses the pandas reader;
    raises ValueError on malformed rows mid-stream (matching the strict
    whole-file native parser's fallback-to-python contract is impossible
    once chunks have been handed out)."""
    lib = _load()
    if lib is None or fmt == "libsvm":
        return None
    cols = ctypes.c_long()
    handle = lib.lgbm_chunk_open(path.encode(), 1 if fmt == "csv" else 2,
                                 int(skip_header), ctypes.byref(cols))
    if not handle:
        return None
    if cols.value <= 0:  # empty file
        lib.lgbm_chunk_close(handle)
        return iter(())

    def gen():
        try:
            while True:
                buf = np.empty((chunk_rows, cols.value), np.float64)
                got = lib.lgbm_chunk_next(
                    handle,
                    buf.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
                    chunk_rows,
                )
                if got < 0:
                    raise ValueError(f"malformed data row in {path}")
                if got == 0:
                    return
                yield buf[:got]
        finally:
            lib.lgbm_chunk_close(handle)

    return gen()


def value_to_bin_numerical(
    X: np.ndarray,
    col_idx: np.ndarray,
    bounds_list: List[np.ndarray],
    out: np.ndarray,
) -> bool:
    """Batch value->bin encode for numerical features into ``out``
    (row-major [n, n_used] u8/u16 slice-compatible array).  Returns False
    when the native path is unavailable (caller uses numpy)."""
    lib = _load()
    if lib is None:
        return False
    if out.dtype == np.uint8:
        is_u16 = 0
    elif out.dtype == np.uint16:
        is_u16 = 1
    else:
        return False
    if not (X.flags.c_contiguous and out.flags.c_contiguous):
        return False
    X = np.ascontiguousarray(X, np.float64)
    col_idx = np.ascontiguousarray(col_idx, np.int64)
    offsets = np.zeros(len(bounds_list) + 1, np.int64)
    for i, b in enumerate(bounds_list):
        offsets[i + 1] = offsets[i] + len(b)
    bounds = (
        np.concatenate(bounds_list).astype(np.float64)
        if bounds_list
        else np.zeros(0, np.float64)
    )
    bounds = np.ascontiguousarray(bounds)
    lib.lgbm_value_to_bin(
        X.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
        X.shape[0], X.shape[1],
        col_idx.ctypes.data_as(ctypes.POINTER(ctypes.c_long)),
        len(col_idx),
        bounds.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
        offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_long)),
        out.ctypes.data_as(ctypes.c_void_p),
        is_u16,
    )
    return True
