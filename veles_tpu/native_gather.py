"""ctypes wrapper over the native multithreaded minibatch gather.

Parity: the native data-path slot of the reference's loaders (SURVEY.md
§2.6 jpegtran/image-codec row — its host hot path was C via cffi). The
packed-memmap pipeline's hot path is a row gather + flip + normalize;
`native/host_gather.cpp` fans it over threads. Python resolves shard
bases + row offsets into flat per-row source addresses, so the C++ side
is shard-agnostic. Built from the committed sources on first use
(`make` decides staleness; `native/build/` is git-ignored). When no
toolchain is available `available()` answers False and callers keep
the numpy path — said ONCE at warning, with make's own error.
"""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import threading
from typing import Optional

import numpy as np

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "native")
_LIB_PATH = os.path.join(_NATIVE_DIR, "build", "libhostgather.so")

_lib = None
_lib_failed = False
_lib_lock = threading.Lock()   # one make/dlopen even under concurrent use

#: thread count for row fan-out; gather is memcpy-bound so a handful of
#: threads saturates memory bandwidth — more just adds join overhead
DEFAULT_THREADS = min(8, os.cpu_count() or 1)


def _load_lib() -> Optional[ctypes.CDLL]:
    global _lib, _lib_failed
    if _lib is not None or _lib_failed:
        return _lib
    with _lib_lock:
        return _load_lib_locked()


def _load_lib_locked() -> Optional[ctypes.CDLL]:
    global _lib, _lib_failed
    if _lib is not None or _lib_failed:   # built while we waited
        return _lib
    try:
        subprocess.run(["make", "-C", _NATIVE_DIR,
                        "build/libhostgather.so"], check=True,
                       capture_output=True)
        lib = ctypes.CDLL(_LIB_PATH)
        lib.hg_gather_u8.argtypes = [
            ctypes.POINTER(ctypes.c_longlong), ctypes.c_int,
            ctypes.c_longlong, ctypes.POINTER(ctypes.c_ubyte),
            ctypes.c_void_p, ctypes.c_int, ctypes.c_int, ctypes.c_int]
        lib.hg_gather_f32.argtypes = [
            ctypes.POINTER(ctypes.c_longlong), ctypes.c_int,
            ctypes.c_longlong, ctypes.POINTER(ctypes.c_float),
            ctypes.c_void_p, ctypes.c_float, ctypes.c_float,
            ctypes.c_void_p, ctypes.c_int, ctypes.c_int, ctypes.c_int]
        _lib = lib
    except (OSError, subprocess.CalledProcessError) as e:
        _lib_failed = True
        detail = (getattr(e, "stderr", b"") or b"").decode(
            errors="replace").strip()[-300:] or str(e)
        logging.getLogger("veles.native_gather").warning(
            "native gather not built (%s): loaders use the numpy "
            "gather instead", detail)
    return _lib


def available() -> bool:
    return _load_lib() is not None


def gather_u8(src_addrs: np.ndarray, row_bytes: int, out: np.ndarray,
              flip: Optional[np.ndarray], w: int, c: int,
              n_threads: int = 0) -> None:
    """Copy len(src_addrs) rows of `row_bytes` bytes from the given
    absolute addresses into `out` (N, row_bytes...) uint8, flipping rows
    where `flip` is set. The source arrays MUST stay alive across the
    call (the loader holds its shard maps)."""
    lib = _load_lib()
    assert lib is not None, "native gather unavailable"
    src = np.ascontiguousarray(src_addrs, np.int64)
    flip_arr = None if flip is None else np.ascontiguousarray(
        flip, np.uint8)  # keep a reference so the pointer stays valid
    lib.hg_gather_u8(
        src.ctypes.data_as(ctypes.POINTER(ctypes.c_longlong)), len(src),
        row_bytes, out.ctypes.data_as(ctypes.POINTER(ctypes.c_ubyte)),
        None if flip_arr is None or not flip_arr.any()
        else flip_arr.ctypes.data_as(ctypes.c_void_p),
        w, c, n_threads or DEFAULT_THREADS)


def gather_f32(src_addrs: np.ndarray, row_bytes: int, out: np.ndarray,
               mean: Optional[np.ndarray], scale: float, offset: float,
               flip: Optional[np.ndarray], w: int, c: int,
               n_threads: int = 0) -> None:
    """gather_u8 + fused uint8 -> float32 `x/scale + offset - mean`
    (division so it is bit-identical to the numpy twin)."""
    lib = _load_lib()
    assert lib is not None, "native gather unavailable"
    src = np.ascontiguousarray(src_addrs, np.int64)
    mean_arr = (None if mean is None
                else np.ascontiguousarray(mean, np.float32))
    flip_arr = None if flip is None else np.ascontiguousarray(
        flip, np.uint8)
    lib.hg_gather_f32(
        src.ctypes.data_as(ctypes.POINTER(ctypes.c_longlong)), len(src),
        row_bytes, out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        None if mean_arr is None
        else mean_arr.ctypes.data_as(ctypes.c_void_p),
        scale, offset,
        None if flip_arr is None or not flip_arr.any()
        else flip_arr.ctypes.data_as(ctypes.c_void_p),
        w, c, n_threads or DEFAULT_THREADS)
