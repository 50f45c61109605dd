"""ctypes loader for the native runtime library (src/native/).

The reference ships one libmxnet.so with a flat C ABI
(include/mxnet/c_api.h); here the native side covers the host runtime —
dependency engine, pooled/shm storage, recordio — while device compute is
JAX/XLA.  No binary is committed: :func:`build` makes each library on
demand from the sources git holds, so what loads always matches the
installed Python and toolchain.  The host runtime has a pure-Python
fallback, so absence of a toolchain only costs speed there, never
functionality.
"""
from __future__ import annotations

import ctypes
import fcntl
import os
import subprocess
import threading

_LIB = None
_TRIED = False
_LOCK = threading.Lock()

_SRC_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src", "native")
_LIB_NAME = "libmxtpu_native.so"


def _declare(lib):
    p = ctypes.POINTER
    lib.MXTEngineCreate.restype = ctypes.c_void_p
    lib.MXTEngineCreate.argtypes = [ctypes.c_int]
    lib.MXTEngineFree.argtypes = [ctypes.c_void_p]
    lib.MXTEngineNewVar.restype = ctypes.c_void_p
    lib.MXTEngineNewVar.argtypes = [ctypes.c_void_p]
    lib.MXTEngineDeleteVar.argtypes = [ctypes.c_void_p, ctypes.c_void_p]
    lib.MXTEnginePushAsync.restype = ctypes.c_int
    lib.MXTEnginePushAsync.argtypes = [
        ctypes.c_void_p, OPR_FN, ctypes.c_void_p,
        p(ctypes.c_void_p), ctypes.c_int,
        p(ctypes.c_void_p), ctypes.c_int, ctypes.c_char_p]
    lib.MXTEngineWaitForVar.restype = ctypes.c_int
    lib.MXTEngineWaitForVar.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                                        ctypes.c_char_p, ctypes.c_int]
    lib.MXTEngineWaitForAll.restype = ctypes.c_int
    lib.MXTEngineWaitForAll.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                        ctypes.c_int]
    lib.MXTEngineOutstanding.restype = ctypes.c_long
    lib.MXTEngineOutstanding.argtypes = [ctypes.c_void_p]

    lib.MXTStorageAlloc.restype = ctypes.c_void_p
    lib.MXTStorageAlloc.argtypes = [ctypes.c_size_t]
    lib.MXTStorageFree.argtypes = [ctypes.c_void_p, ctypes.c_size_t]
    lib.MXTStorageEmptyCache.argtypes = []
    lib.MXTStoragePooledBytes.restype = ctypes.c_size_t

    lib.MXTShmCreate.restype = ctypes.c_void_p
    lib.MXTShmCreate.argtypes = [ctypes.c_char_p, ctypes.c_size_t]
    lib.MXTShmAttach.restype = ctypes.c_void_p
    lib.MXTShmAttach.argtypes = [ctypes.c_char_p, ctypes.c_size_t]
    lib.MXTShmDetach.restype = ctypes.c_int
    lib.MXTShmDetach.argtypes = [ctypes.c_void_p, ctypes.c_size_t]
    lib.MXTShmUnlink.restype = ctypes.c_int
    lib.MXTShmUnlink.argtypes = [ctypes.c_char_p]

    lib.MXTRecordIOWriterCreate.restype = ctypes.c_void_p
    lib.MXTRecordIOWriterCreate.argtypes = [ctypes.c_char_p]
    lib.MXTRecordIOWriterWrite.restype = ctypes.c_int
    lib.MXTRecordIOWriterWrite.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                           ctypes.c_size_t]
    lib.MXTRecordIOWriterTell.restype = ctypes.c_long
    lib.MXTRecordIOWriterTell.argtypes = [ctypes.c_void_p]
    lib.MXTRecordIOWriterFree.argtypes = [ctypes.c_void_p]
    lib.MXTRecordIOReaderCreate.restype = ctypes.c_void_p
    lib.MXTRecordIOReaderCreate.argtypes = [ctypes.c_char_p]
    lib.MXTRecordIOReaderRead.restype = ctypes.c_int
    lib.MXTRecordIOReaderRead.argtypes = [
        ctypes.c_void_p, p(ctypes.c_char_p), p(ctypes.c_size_t)]
    lib.MXTRecordIOReaderSeek.restype = ctypes.c_int
    lib.MXTRecordIOReaderSeek.argtypes = [ctypes.c_void_p, ctypes.c_long]
    lib.MXTRecordIOReaderTell.restype = ctypes.c_long
    lib.MXTRecordIOReaderTell.argtypes = [ctypes.c_void_p]
    lib.MXTRecordIOReaderFree.argtypes = [ctypes.c_void_p]


OPR_FN = ctypes.CFUNCTYPE(ctypes.c_int, ctypes.c_void_p)


def _fresh(path: str) -> bool:
    """``path`` exists and is no older than any source beside it — make's
    own rule, applied to every source so it errs towards rebuilding."""
    if not os.path.exists(path):
        return False
    srcs = [os.path.join(_SRC_DIR, f) for f in os.listdir(_SRC_DIR)
            if f == "Makefile" or f.endswith((".cc", ".h"))]
    return os.path.getmtime(path) >= max(map(os.path.getmtime, srcs))


def build(target: str = _LIB_NAME, timeout: float = 600.0) -> str:
    """Build ``src/native/<target>`` from the committed sources and return
    its path.  The ONE way a native binary comes to exist — the loader
    below, the C-ABI and cpp-package tests and
    ``tools/make_serving_bundle.py`` all come through here — and one build
    at a time: pytest-xdist workers and tools race for the same outputs,
    so the look and the make run under an exclusive file lock.  A library
    that is already up to date is returned as it is, so a checkout that
    was built once needs neither ``make`` nor a writable ``src/native``.
    Raises ``OSError`` / ``subprocess.SubprocessError`` when a build is
    needed and there is no toolchain or it fails."""
    path = os.path.join(_SRC_DIR, target)
    try:
        lock = open(os.path.join(_SRC_DIR, ".build.lock"), "w")
    except OSError:
        # a read-only checkout: nobody can be half-way through a link
        if _fresh(path):
            return path
        raise
    with lock:
        fcntl.flock(lock, fcntl.LOCK_EX)
        if _fresh(path):
            return path
        run = subprocess.run(["make", "-C", _SRC_DIR, target],
                             capture_output=True, text=True, timeout=timeout)
    if run.returncode != 0:
        raise subprocess.CalledProcessError(
            run.returncode, run.args, run.stdout, run.stderr[-4000:])
    return path


def get_lib():
    """Load (building if needed) the native library; None if unavailable."""
    global _LIB, _TRIED
    if _LIB is not None or _TRIED:
        return _LIB
    with _LOCK:
        if _LIB is not None or _TRIED:
            return _LIB
        _TRIED = True
        try:
            lib = ctypes.CDLL(build(_LIB_NAME))
        except (OSError, subprocess.SubprocessError):
            return None  # no toolchain: the pure-Python host runtime
        _declare(lib)
        _LIB = lib
    return _LIB
