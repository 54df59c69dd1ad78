"""Build + ctypes binding for the native shared-memory window backend.

Compiled on demand with g++ (no pybind11 in this image; the C ABI +
ctypes is all the binding this needs). The .so is cached next to the
source and rebuilt when the source is newer.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "spwindow.cpp")
_SO = os.path.join(_HERE, "libspwindow.so")
_lock = threading.Lock()
_lib = None


def _build():
    import sys

    # build beside the target and rename into place: several processes
    # (test workers, spawned spokes) may find the library missing at
    # once, and none may load a half-written file
    tmp = f"{_SO}.{os.getpid()}.tmp"
    cmd = ["g++", "-O2", "-std=c++17", "-shared", "-fPIC", "-o", tmp,
           _SRC]
    if sys.platform.startswith("linux"):
        # shm_open/shm_unlink live in librt on pre-2.34 glibc (the flag
        # is harmless where they moved into libc); macOS has no librt
        # and keeps them in libc, so the flag must stay Linux-only
        cmd.append("-lrt")
    try:
        r = subprocess.run(cmd, capture_output=True, text=True)
        if r.returncode != 0:
            raise RuntimeError(
                f"building {_SO} failed (rc {r.returncode}): "
                f"{' '.join(cmd)}\n{r.stdout}{r.stderr}")
        os.replace(tmp, _SO)
    finally:
        if os.path.exists(tmp):
            os.remove(tmp)


def load():
    """Compile (if stale) and load the spwindow library."""
    global _lib
    with _lock:
        if _lib is not None:
            return _lib
        if (not os.path.exists(_SO)
                or os.path.getmtime(_SO) < os.path.getmtime(_SRC)):
            _build()
        try:
            lib = ctypes.CDLL(_SO)
        except OSError:
            # a prebuilt .so from a different toolchain (e.g. missing
            # the librt link, surfacing as "undefined symbol:
            # shm_open") — rebuild in place for THIS toolchain
            _build()
            lib = ctypes.CDLL(_SO)
        lib.spw_create.restype = ctypes.c_void_p
        lib.spw_create.argtypes = [ctypes.c_char_p, ctypes.c_int64]
        lib.spw_open.restype = ctypes.c_void_p
        lib.spw_open.argtypes = [ctypes.c_char_p, ctypes.c_int64]
        lib.spw_put.restype = None
        lib.spw_put.argtypes = [ctypes.c_void_p,
                                ctypes.POINTER(ctypes.c_double),
                                ctypes.c_int64]
        lib.spw_read.restype = ctypes.c_int64
        lib.spw_read.argtypes = [ctypes.c_void_p,
                                 ctypes.POINTER(ctypes.c_double),
                                 ctypes.c_int64]
        lib.spw_read_id.restype = ctypes.c_int64
        lib.spw_read_id.argtypes = [ctypes.c_void_p]
        lib.spw_kill.restype = None
        lib.spw_kill.argtypes = [ctypes.c_void_p]
        lib.spw_close.restype = None
        lib.spw_close.argtypes = [ctypes.c_void_p, ctypes.c_int]
        _lib = lib
        return _lib
