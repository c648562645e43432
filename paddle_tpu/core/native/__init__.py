"""Native (C++) runtime components, built on first import.

The reference implements its runtime substrate in C++ (store: N12
`tcp_store.h:121`; host tracer: N34 `host_tracer.cc`). These are the
TPU-native equivalents, compiled from the sources in this directory with
g++ into one shared library and bound via ctypes (the environment has no
pybind11 — ctypes is the sanctioned binding path).

Falls back cleanly (``LIB is None``) if no toolchain is available;
pure-Python equivalents in distributed/store.py and profiler keep the
API working.
"""
from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import tempfile

_DIR = os.path.dirname(os.path.abspath(__file__))
_LIB_NAME = "libpaddle_tpu_native.so"

LIB = None


def _sources():
    return [os.path.join(_DIR, f) for f in sorted(os.listdir(_DIR))
            if f.endswith(".cc")]


def _build(lib_path):
    srcs = _sources()
    cmd = ["g++", "-O2", "-fPIC", "-shared", "-std=c++17", "-pthread",
           "-o", lib_path] + srcs
    subprocess.run(cmd, check=True, capture_output=True, timeout=240)


def _sources_digest(srcs):
    """sha256 over the source files' names and bytes: the binary's key.
    mtimes do not survive a copy of the tree (git ignores the binary but
    a copied checkout carries it), so a stale library could otherwise
    load against newer sources."""
    h = hashlib.sha256()
    for path in srcs:
        h.update(os.path.basename(path).encode() + b"\0")
        with open(path, "rb") as f:
            h.update(f.read())
    return h.hexdigest()[:16]


def _load():
    global LIB
    srcs = _sources()
    if not srcs:
        return None
    # the digest rides in the file name: a library built from other
    # sources is simply not the file this import looks for
    stem, ext = os.path.splitext(_LIB_NAME)
    lib_path = os.path.join(_DIR, f"{stem}.{_sources_digest(srcs)}{ext}")
    if not os.path.exists(lib_path):
        tmp = None
        try:
            # build into a temp file then atomically rename, so concurrent
            # importers never load a half-written library
            fd, tmp = tempfile.mkstemp(suffix=".so", dir=_DIR)
            os.close(fd)
            _build(tmp)
            os.replace(tmp, lib_path)
        except Exception:
            if tmp is not None:
                try:
                    os.unlink(tmp)
                except OSError:
                    pass
            return None
        for f in os.listdir(_DIR):  # libraries of older sources
            if (f.startswith(stem + ".") and f.endswith(ext)
                    and os.path.join(_DIR, f) != lib_path):
                try:
                    os.unlink(os.path.join(_DIR, f))
                except OSError:
                    pass
    try:
        lib = ctypes.CDLL(lib_path)
    except OSError:
        return None

    lib.pt_store_server_start.restype = ctypes.c_void_p
    lib.pt_store_server_start.argtypes = [ctypes.c_int,
                                          ctypes.POINTER(ctypes.c_int)]
    lib.pt_store_server_stop.argtypes = [ctypes.c_void_p]

    lib.pt_tracer_enable.argtypes = [ctypes.c_int]
    lib.pt_tracer_enabled.restype = ctypes.c_int
    lib.pt_tracer_now_ns.restype = ctypes.c_int64
    lib.pt_tracer_record.argtypes = [
        ctypes.c_char_p, ctypes.c_int64, ctypes.c_int64,
        ctypes.c_int32, ctypes.c_int32,
    ]
    lib.pt_tracer_count.restype = ctypes.c_size_t
    lib.pt_tracer_drain.restype = ctypes.c_size_t
    lib.pt_tracer_drain.argtypes = [
        ctypes.c_char_p,
        ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int32),
        ctypes.c_size_t,
    ]
    return lib


LIB = _load()


def available() -> bool:
    return LIB is not None


# ----------------------------------------------------------------- tracer

def tracer_enable(on=True):
    if LIB is not None:
        LIB.pt_tracer_enable(1 if on else 0)


def tracer_record(name: str, start_ns: int, end_ns: int, tid: int = 0,
                  kind: int = 0):
    if LIB is not None:
        LIB.pt_tracer_record(name.encode()[:63], start_ns, end_ns, tid, kind)


def tracer_now_ns() -> int:
    if LIB is not None:
        return LIB.pt_tracer_now_ns()
    import time

    return time.monotonic_ns()


def tracer_drain(cap=1 << 20):
    """Drain recorded events -> list of (name, start_ns, end_ns, tid, kind)."""
    if LIB is None:
        return []
    n = LIB.pt_tracer_count()
    if n == 0:
        return []
    cap = min(int(n), cap)
    names = ctypes.create_string_buffer(cap * 64)
    starts = (ctypes.c_int64 * cap)()
    ends = (ctypes.c_int64 * cap)()
    tids = (ctypes.c_int32 * cap)()
    kinds = (ctypes.c_int32 * cap)()
    got = LIB.pt_tracer_drain(names, starts, ends, tids, kinds, cap)
    out = []
    for i in range(got):
        raw = names.raw[i * 64:(i + 1) * 64]
        nm = raw.split(b"\0", 1)[0].decode(errors="replace")
        out.append((nm, starts[i], ends[i], tids[i], kinds[i]))
    return out
