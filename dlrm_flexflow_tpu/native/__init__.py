"""Native (C++) runtime components, built on demand with g++.

The reference keeps its simulator engine and data loaders in native code
(reference: src/runtime/simulator.cc, python/flexflow_dataloader.cc); this
package does the same for the TPU build. Sources live next to this file
(ffsim.cc, ffloader.cc) and are compiled into one shared library
`_ffnative-<hash>.so` at first use; consumers (search/simulator.py,
data/dataloader.py) fall back to pure-Python paths when the toolchain is
unavailable, so the framework never hard-requires a compiler.

The library's name carries a hash of its sources, so a library is only
ever loaded for the sources it was built from — a copied checkout
(whose mtimes mean nothing) rebuilds exactly when the sources differ.
"""

from __future__ import annotations

import contextlib
import ctypes
import glob
import hashlib
import os
import subprocess
import threading

_DIR = os.path.dirname(os.path.abspath(__file__))
_SOURCES = ["ffsim.cc", "ffloader.cc", "ffemb.cc"]

_lock = threading.Lock()
_lib = None
_load_failed = False


def _lib_path() -> str:
    h = hashlib.sha256()
    for s in _SOURCES:
        with open(os.path.join(_DIR, s), "rb") as f:
            h.update(f.read())
    return os.path.join(_DIR, f"_ffnative-{h.hexdigest()[:16]}.so")


def _build(lib_path: str) -> None:
    # compile to a per-pid temp file then rename: rename is atomic, so a
    # concurrent process never dlopens a half-written .so
    tmp = f"{lib_path}.{os.getpid()}.tmp"
    cmd = ["g++", "-O2", "-std=c++17", "-shared", "-fPIC", "-pthread",
           "-o", tmp] + [os.path.join(_DIR, s) for s in _SOURCES]
    try:
        subprocess.run(cmd, check=True, capture_output=True, text=True)
        os.replace(tmp, lib_path)
    finally:
        if os.path.exists(tmp):
            os.remove(tmp)
    # libraries of other source versions are never loaded again
    for stale in glob.glob(os.path.join(_DIR, "_ffnative*.so")):
        if stale != lib_path:
            with contextlib.suppress(FileNotFoundError):  # lost a race
                os.remove(stale)


def _bind(lib: ctypes.CDLL) -> ctypes.CDLL:
    c = ctypes
    lib.ffsim_makespan.restype = c.c_double
    lib.ffsim_makespan.argtypes = [
        c.c_int64, c.POINTER(c.c_double), c.POINTER(c.c_int32),
        c.c_int64, c.POINTER(c.c_int64), c.POINTER(c.c_int64)]
    lib.ffloader_open.restype = c.c_void_p
    lib.ffloader_open.argtypes = [c.c_char_p, c.c_int64, c.c_int32,
                                  c.c_uint64]
    lib.ffloader_meta.restype = None
    lib.ffloader_meta.argtypes = [c.c_void_p, c.POINTER(c.c_int64)]
    lib.ffloader_next.restype = c.c_int64
    lib.ffloader_next.argtypes = [c.c_void_p, c.POINTER(c.c_float),
                                  c.POINTER(c.c_int32), c.POINTER(c.c_float)]
    lib.ffloader_close.restype = None
    lib.ffloader_close.argtypes = [c.c_void_p]
    lib.ffemb_bag_gather.restype = None
    lib.ffemb_bag_gather.argtypes = [
        c.POINTER(c.c_float), c.c_int64, c.c_int64,
        c.POINTER(c.c_int64), c.c_int64, c.c_int64, c.c_int32,
        c.POINTER(c.c_float)]
    lib.ffemb_bag_scatter.restype = None
    lib.ffemb_bag_scatter.argtypes = [
        c.POINTER(c.c_float), c.c_int64, c.c_int64,
        c.POINTER(c.c_int64), c.c_int64, c.c_int64, c.c_int32,
        c.POINTER(c.c_float), c.c_float]
    return lib


def get_lib():
    """The bound native library, or None if it cannot be built/loaded."""
    global _lib, _load_failed
    if _lib is not None or _load_failed:
        return _lib
    with _lock:
        if _lib is not None or _load_failed:
            return _lib
        try:
            lib_path = _lib_path()
            if not os.path.exists(lib_path):
                _build(lib_path)
            _lib = _bind(ctypes.CDLL(lib_path))
        except (OSError, subprocess.CalledProcessError, AttributeError):
            _load_failed = True
    return _lib


def available() -> bool:
    return get_lib() is not None
