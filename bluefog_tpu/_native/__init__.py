"""Native (C++) runtime components, bound via ctypes.

The reference's runtime core is C++ (SURVEY.md §2.1); the TPU compute path
here is XLA, but the host-side runtime pieces that benefit from native code
are implemented in C++ as well:

* ``timeline.cc`` — chrome-trace writer with a ring buffer + flush thread
  (reference: ``common/timeline.{h,cc}``'s spsc queue + TimelineWriter).
* ``schedule.cc`` — edge -> ppermute-round coloring for large topologies
  (reference: graph-communicator construction, ``mpi_context.cc:412-430``).
* ``loader.cc`` — multi-threaded batch row-gather for the input pipeline
  (reference: the role of torch DataLoader worker processes).

The shared library is built on demand with ``g++`` (no pip/pybind needed —
plain ``extern "C"`` + ctypes) and cached next to the sources.  Every entry
point has a pure-Python fallback, so the package works without a toolchain.
"""
from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import List, Optional, Sequence, Tuple

_HERE = os.path.dirname(os.path.abspath(__file__))
_SOURCES = ("timeline.cc", "schedule.cc", "loader.cc")


def _lib_path() -> str:
    """The library is named after a hash of its sources: a build is stale
    exactly when the sources changed.  (Modification times do not survive a
    copy of the tree, and a copied ``.so`` may predate the sources.)"""
    h = hashlib.sha256()
    for s in _SOURCES:
        with open(os.path.join(_HERE, s), "rb") as f:
            h.update(f.read())
    return os.path.join(_HERE, f"libbft_native.{h.hexdigest()[:12]}.so")


_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_build_failed = False


def _build(lib_path: str) -> bool:
    srcs = [os.path.join(_HERE, s) for s in _SOURCES]
    tmp = f"{lib_path}.tmp{os.getpid()}"     # concurrent builders never
    cmd = ["g++", "-O2", "-std=c++17", "-shared", "-fPIC",    # share a file
           "-o", tmp] + srcs + ["-lpthread"]
    try:
        r = subprocess.run(cmd, capture_output=True, timeout=120)
        if r.returncode != 0:
            return False
        os.replace(tmp, lib_path)
        return True
    except (OSError, subprocess.TimeoutExpired):
        return False
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)


def load() -> Optional[ctypes.CDLL]:
    """Load (building if needed) the native library; None when unavailable."""
    global _lib, _build_failed
    with _lock:
        if _lib is not None:
            return _lib
        if _build_failed:
            return None
        lib_path = _lib_path()
        if not os.path.exists(lib_path) and not _build(lib_path):
            _build_failed = True
            return None
        try:
            lib = ctypes.CDLL(lib_path)
        except OSError:
            _build_failed = True
            return None
        lib.bft_timeline_start.argtypes = [ctypes.c_char_p]
        lib.bft_timeline_start.restype = ctypes.c_int
        lib.bft_timeline_record.argtypes = [
            ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char,
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int32, ctypes.c_int32]
        lib.bft_timeline_record.restype = ctypes.c_int
        lib.bft_timeline_stop.argtypes = []
        lib.bft_timeline_stop.restype = ctypes.c_int64
        lib.bft_timeline_dropped.argtypes = []
        lib.bft_timeline_dropped.restype = ctypes.c_int64
        lib.bft_color_edges.argtypes = [
            ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int32),
            ctypes.c_int64, ctypes.c_int32, ctypes.POINTER(ctypes.c_int32)]
        lib.bft_color_edges.restype = ctypes.c_int32
        lib.bft_gather_rows.argtypes = [
            ctypes.c_char_p, ctypes.c_char_p, ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int64), ctypes.c_int64, ctypes.c_int64,
            ctypes.c_int32]
        lib.bft_gather_rows.restype = ctypes.c_int32
        _lib = lib
        return _lib


def available() -> bool:
    return load() is not None


# ---------------------------------------------------------------------------
# schedule: native edge coloring
# ---------------------------------------------------------------------------

def color_edges_native(
    edges: Sequence[Tuple[int, int]], size: int,
) -> Optional[List[List[Tuple[int, int]]]]:
    """Native edge->round partitioning; None when the library is unavailable.

    Output contract matches ``schedule.color_edges`` (same greedy order).
    """
    lib = load()
    if lib is None:
        return None
    import numpy as np

    dedup = sorted(set((int(s), int(d)) for s, d in edges))
    n = len(dedup)
    srcs = np.asarray([e[0] for e in dedup], dtype=np.int32)
    dsts = np.asarray([e[1] for e in dedup], dtype=np.int32)
    out = np.empty(n, dtype=np.int32)
    n_rounds = lib.bft_color_edges(
        srcs.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        dsts.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        n, size, out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)))
    if n_rounds < 0:
        raise ValueError("invalid edge set (self-loop or rank out of range)")
    rounds: List[List[Tuple[int, int]]] = [[] for _ in range(n_rounds)]
    # rebuild each round in the colorer's processing order
    order = sorted(range(n),
                   key=lambda i: ((dedup[i][1] - dedup[i][0]) % size, dedup[i][0]))
    for i in order:
        rounds[int(out[i])].append(dedup[i])
    return rounds


# ---------------------------------------------------------------------------
# loader: native multi-threaded row gather
# ---------------------------------------------------------------------------

def gather_rows_native(src, idx, threads: int = 4):
    """``src[idx]`` for row indices via the native thread-pool memcpy engine.

    Returns None when the library is unavailable or the layout is not a
    plain C-contiguous row gather (callers fall back to numpy).
    """
    lib = load()
    if lib is None:
        return None
    import numpy as np

    src = np.asarray(src)
    # raw-memcpy engine: refuse layouts it cannot handle rather than pay a
    # hidden whole-array copy (non-contiguous) or corrupt refcounts (object
    # dtype) — callers fall back to numpy
    if src.dtype.hasobject or not src.flags.c_contiguous or src.ndim < 1:
        return None
    idx = np.asarray(idx)
    # bool masks and float indices mean something different (or error) under
    # numpy — only integer row gathers belong to this engine
    if idx.dtype == np.bool_ or not np.issubdtype(idx.dtype, np.integer):
        return None
    flat_idx = np.ascontiguousarray(idx, dtype=np.int64).reshape(-1)
    # numpy row-gather semantics: negative indices wrap
    flat_idx = np.where(flat_idx < 0, flat_idx + src.shape[0], flat_idx)
    row_bytes = src.dtype.itemsize * int(np.prod(src.shape[1:], dtype=np.int64))
    if row_bytes <= 0:
        return None
    dst = np.empty((flat_idx.size,) + src.shape[1:], dtype=src.dtype)
    rc = lib.bft_gather_rows(
        dst.ctypes.data_as(ctypes.c_char_p),
        src.ctypes.data_as(ctypes.c_char_p),
        row_bytes,
        flat_idx.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        flat_idx.size, src.shape[0], int(threads))
    if rc != 0:
        raise IndexError("gather index out of range")
    return dst.reshape(tuple(np.shape(idx)) + src.shape[1:])


# ---------------------------------------------------------------------------
# timeline: native writer
# ---------------------------------------------------------------------------

def timeline_start(path: str) -> bool:
    lib = load()
    return bool(lib and lib.bft_timeline_start(path.encode()))


def timeline_record(name: str, cat: str, ph: str, ts_us: int,
                    dur_us: int = 0, pid: int = 0, tid: int = 0) -> bool:
    lib = load()
    return bool(lib and lib.bft_timeline_record(
        name.encode(), cat.encode(), ph.encode(), int(ts_us), int(dur_us),
        int(pid), int(tid)))


def timeline_stop() -> int:
    """Stop + flush; returns dropped-event count (-1 if not running)."""
    lib = load()
    return int(lib.bft_timeline_stop()) if lib else -1
