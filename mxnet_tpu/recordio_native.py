"""ctypes loader for the native RecordIO core (src/recordio_core.cc).

The C++ scanner/reader is the data pipeline's high-throughput path: a
whole-file index scan and random-access record reads with no Python
per-frame overhead. Built on demand with g++ (cached next to the source
as a .so named by the source's content hash, so a library is only ever
loaded if it was built from the source as it stands — file times mean
nothing after a checkout or a copy of the tree); every entry point
degrades to the pure-python
implementation in `mxnet_tpu.recordio` when the toolchain or the build
is unavailable — the wire format is identical.
"""
from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading

__all__ = ["available", "native_index", "native_read_at"]

_SRC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src", "recordio_core.cc")
_lock = threading.Lock()
_lib = None
_tried = False

_ERRORS = {-1: "cannot open file", -2: "invalid RecordIO magic",
           -3: "truncated record", -4: "capacity exceeded"}


def _load():
    global _lib, _tried
    with _lock:
        if _tried:
            return _lib
        _tried = True
        try:
            with open(_SRC, "rb") as f:
                digest = hashlib.sha256(f.read()).hexdigest()[:16]
            so = "%s.%s.so" % (os.path.splitext(_SRC)[0], digest)
            if not os.path.exists(so):
                # build to a private temp path, then atomically rename:
                # concurrent processes (DataLoader workers, parallel
                # pytest) must never dlopen a half-written .so — the
                # per-process lock cannot serialize across processes
                tmp = "%s.build.%d" % (so, os.getpid())
                try:
                    subprocess.run(
                        ["g++", "-O2", "-shared", "-fPIC", "-std=c++17",
                         _SRC, "-o", tmp],
                        check=True, capture_output=True, timeout=120)
                    os.replace(tmp, so)
                finally:
                    if os.path.exists(tmp):
                        os.unlink(tmp)
            lib = ctypes.CDLL(so)
            # binding stays inside the try: a stale .so missing a
            # symbol must degrade to the python fallback, not raise
            lib.rio_index.restype = ctypes.c_longlong
            lib.rio_index.argtypes = [
                ctypes.c_char_p, ctypes.POINTER(ctypes.c_ulonglong),
                ctypes.c_ulonglong]
            lib.rio_read_at.restype = ctypes.c_int
            lib.rio_read_at.argtypes = [
                ctypes.c_char_p, ctypes.c_ulonglong,
                ctypes.POINTER(ctypes.c_ubyte), ctypes.c_ulonglong,
                ctypes.POINTER(ctypes.c_ulonglong),
                ctypes.POINTER(ctypes.c_ulonglong)]
        except (OSError, subprocess.SubprocessError,
                FileNotFoundError, AttributeError):
            return None
        _lib = lib
        return _lib


def available():
    """True when the native core is built and loadable."""
    return _load() is not None


def _check(rc, path):
    if rc < 0:
        raise IOError("%s: %s" % (_ERRORS.get(rc, "error %d" % rc), path))


def native_index(path):
    """Offsets of every logical record in a .rec file (native scan).
    Returns a list of byte offsets; raises IOError on corrupt files."""
    lib = _load()
    if lib is None:
        raise RuntimeError("native recordio core unavailable")
    path_b = os.fsencode(os.fspath(path))
    # single pass with a bounded buffer: size//8 bounds the record
    # count (every frame costs >= 8 bytes) but allocating that many
    # slots would equal the FILE size in RAM for huge .recs — cap the
    # buffer and fall back to an exact count+fill double scan only in
    # the many-tiny-records regime that overflows it.
    cap = max(1, min(os.path.getsize(path) // 8, 1 << 24))
    arr = (ctypes.c_ulonglong * cap)()
    n = lib.rio_index(path_b, arr, cap)
    if n == -4:
        n = lib.rio_index(path_b, None, 0)        # exact count
        _check(n, path)
        arr = (ctypes.c_ulonglong * n)()
        n = lib.rio_index(path_b, arr, n)
    _check(n, path)
    return list(arr[:n])


_tls = threading.local()


def _scratch(cap):
    """Reusable per-thread read buffer (a fresh ctypes buffer is
    zero-initialized every call — measurable on per-frame hot paths)."""
    buf = getattr(_tls, "buf", None)
    if buf is None or len(buf) < cap:
        buf = (ctypes.c_ubyte * cap)()
        _tls.buf = buf
    return buf


def native_read_at(path, offset):
    """One logical record (continuation chunks reassembled) starting at
    `offset`. Returns (bytes, end_offset) where end_offset is the file
    position just past the record — callers mirroring a sequential
    handle seek there."""
    lib = _load()
    if lib is None:
        raise RuntimeError("native recordio core unavailable")
    path_b = os.fsencode(os.fspath(path))
    # one parse in the common case: try a typical-record buffer; on
    # capacity miss the call still walked the chunks and reported the
    # exact length, so a single retry suffices.
    length = ctypes.c_ulonglong()
    end = ctypes.c_ulonglong()
    cap = 1 << 20
    buf = _scratch(cap)
    rc = lib.rio_read_at(path_b, offset, buf, len(buf),
                         ctypes.byref(length), ctypes.byref(end))
    if rc == -4:
        buf = _scratch(length.value)
        rc = lib.rio_read_at(path_b, offset, buf, len(buf),
                             ctypes.byref(length), ctypes.byref(end))
    _check(rc, path)
    return bytes(buf[:length.value]), end.value
