"""ctypes bindings for the in-tree C++ components (native/).

The shared library is built lazily with g++ on first use and cached next to
the sources (``native/build/``). Pure-Python fallbacks exist for every native
entry point (distegnn_tpu/data/partition.py), so the framework runs even
where no compiler is available — mirroring how the reference degrades from
torch-sparse METIS to its other splitters."""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import Optional

import numpy as np

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "native")
_BUILD_DIR = os.path.join(_NATIVE_DIR, "build")
_LIB_PATH = os.path.join(_BUILD_DIR, "libdistegnn_native.so")
_STAMP_PATH = _LIB_PATH + ".sha256"
_SOURCES = ("partition.cpp", "blockify.cpp")
_lock = threading.Lock()
_lib = None
_build_error: Optional[str] = None


def _source_digest() -> str:
    """sha256 over the sources' CONTENT: a copied or freshly checked-out tree
    does not keep mtimes, so staleness cannot be read from them."""
    h = hashlib.sha256()
    for name in _SOURCES:
        with open(os.path.join(_NATIVE_DIR, name), "rb") as f:
            h.update(name.encode() + b"\0" + f.read() + b"\0")
    return h.hexdigest()


def _build(digest: str) -> None:
    """Compile the library and stamp it with the sources' digest; raises
    RuntimeError naming why (no g++, compile error, timeout)."""
    os.makedirs(_BUILD_DIR, exist_ok=True)
    srcs = [os.path.join(_NATIVE_DIR, s) for s in _SOURCES]
    tmp = f"{_LIB_PATH}.{os.getpid()}.tmp"
    cmd = ["g++", "-O3", "-shared", "-fPIC", "-std=c++17", *srcs, "-o", tmp]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
    except FileNotFoundError as exc:
        raise RuntimeError("g++ not found") from exc
    except subprocess.CalledProcessError as exc:
        raise RuntimeError(
            f"g++ failed: {exc.stderr.decode(errors='replace')[-500:]}") from exc
    except subprocess.TimeoutExpired as exc:
        raise RuntimeError("g++ timed out after 120 s") from exc
    os.replace(tmp, _LIB_PATH)   # atomic: a concurrent loader never sees half
    with open(_STAMP_PATH, "w") as f:
        f.write(digest)


def _stamp() -> Optional[str]:
    try:
        with open(_STAMP_PATH) as f:
            return f.read().strip()
    except OSError:
        return None


def load_native() -> Optional[ctypes.CDLL]:
    """The native library, (re)built when ``native/build`` holds none built
    from the current sources; None if it cannot be built — the callers then
    take their NumPy fallbacks, and :func:`native_status` says why."""
    global _lib, _build_error
    with _lock:
        if _lib is not None or _build_error is not None:
            return _lib
        try:
            digest = _source_digest()
            if not os.path.exists(_LIB_PATH) or _stamp() != digest:
                _build(digest)
            _lib = _bind(ctypes.CDLL(_LIB_PATH))
        except (OSError, AttributeError, RuntimeError) as exc:
            _build_error = f"{type(exc).__name__}: {exc}"
        return _lib


def native_status() -> str:
    """'native' when the C++ library is loaded, else 'numpy fallback
    (<reason>)' — what the entry points print so that a missing compiler is
    visible, not silent."""
    if load_native() is not None:
        return "native"
    return f"numpy fallback ({_build_error})"


def _bind(lib: ctypes.CDLL) -> ctypes.CDLL:
    """Declare every exported symbol's signature (AttributeError if one is
    missing)."""
    lib.partition_graph.restype = ctypes.c_int
    lib.partition_graph.argtypes = [
        ctypes.c_int64,
        np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS"),
        np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS"),
        ctypes.c_int32, ctypes.c_uint64,
        np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
    ]
    lib.edge_cut.restype = ctypes.c_int64
    lib.edge_cut.argtypes = [
        ctypes.c_int64,
        np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS"),
        np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS"),
        np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
    ]
    lib.blockify_edges_native.restype = ctypes.c_int
    lib.blockify_edges_native.argtypes = [
        ctypes.c_int64,
        np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS"),
        np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS"),
        ctypes.c_void_p,  # attr (may be NULL)
        ctypes.c_int64, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
        np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
        np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS"),
        np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS"),
    ]
    lib.pairing_perm_native.restype = ctypes.c_int
    lib.pairing_perm_native.argtypes = [
        ctypes.c_int64,
        np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
        np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
        np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS"),
    ]
    return lib


def native_partition(indptr: np.ndarray, indices: np.ndarray, nparts: int,
                     seed: int = 0) -> Optional[np.ndarray]:
    """Balanced k-way partition labels [n] via the C++ partitioner, or None
    when the native library can't be built."""
    lib = load_native()
    if lib is None:
        return None
    n = indptr.shape[0] - 1
    labels = np.empty(n, np.int32)
    rc = lib.partition_graph(n, np.ascontiguousarray(indptr, np.int64),
                             np.ascontiguousarray(indices, np.int64),
                             np.int32(nparts), np.uint64(seed), labels)
    return labels if rc == 0 else None


def native_edge_cut(indptr: np.ndarray, indices: np.ndarray,
                    labels: np.ndarray) -> Optional[int]:
    lib = load_native()
    if lib is None:
        return None
    n = indptr.shape[0] - 1
    return int(lib.edge_cut(n, np.ascontiguousarray(indptr, np.int64),
                            np.ascontiguousarray(indices, np.int64),
                            np.ascontiguousarray(labels, np.int32)))


def native_blockify(edge_index: np.ndarray, edge_attr: Optional[np.ndarray],
                    n_nodes: int, epb: int, block: int):
    """Blocked edge re-layout via C++ (ops/blocked.blockify_edges semantics),
    or None when the native library can't be built / input is invalid."""
    lib = load_native()
    if lib is None:
        return None
    e = edge_index.shape[1]
    nb = n_nodes // block
    E = nb * epb
    d = edge_attr.shape[1] if edge_attr is not None else 0
    out_index = np.empty((2, E), np.int32)
    # d == 0: C++ never touches out_attr, a 1-element dummy satisfies ctypes
    out_attr = np.zeros((E, d) if d else (1, 1), np.float32)
    out_mask = np.empty((E,), np.float32)
    row = np.ascontiguousarray(edge_index[0], np.int64)
    col = np.ascontiguousarray(edge_index[1], np.int64)
    # keep the contiguous attr alive across the call (a bare .ctypes.data of
    # a temporary would dangle)
    attr_arr = np.ascontiguousarray(edge_attr, np.float32) if d else None
    rc = lib.blockify_edges_native(
        e, row, col, attr_arr.ctypes.data if d else None, d, n_nodes, block,
        epb, out_index, out_attr, out_mask)
    if rc != 0:
        return None
    return out_index, out_attr if d else np.zeros((E, 0), np.float32), out_mask


def native_pairing(edge_index: np.ndarray):
    """Reverse-edge involution via C++ (ops/blocked.pairing_perm semantics).

    Tri-state: ndarray (valid permutation) | False (definitively asymmetric)
    | None (native unavailable or ids out of packing range — use the numpy
    path). Prefer ops/blocked.pairing_perm_fast, which folds the dispatch."""
    lib = load_native()
    if lib is None:
        return None
    e = edge_index.shape[1]
    pair = np.empty((e,), np.int64)
    rc = lib.pairing_perm_native(
        e, np.ascontiguousarray(edge_index[0], np.int32),
        np.ascontiguousarray(edge_index[1], np.int32), pair)
    if rc == 0:
        return pair
    if rc == 1:
        return False           # definitively not symmetric
    return None                # out of packing range: caller uses numpy
