"""ctypes loader for the native (C++) IO layer.

Reference: the reference's IO is C++ (kaminpar-io/metis_parser.cc mmap
tokenizer); this is the TPU build's native equivalent.  The shared library
is built lazily with g++ next to its source (``_native/metis_native.so``,
rebuilt when the source is newer) and loaded via ctypes — no
pybind11/Python-C-API dependency.  Every entry degrades to
the pure-NumPy parser when the toolchain or build is unavailable
(KAMINPAR_TPU_NO_NATIVE=1 forces the fallback).
"""

from __future__ import annotations

import ctypes
import os
import subprocess

import numpy as np

_SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "_native",
                    "metis_native.cpp")
_SO = os.path.splitext(_SRC)[0] + ".so"
_lib = None
_lib_failed = False


class _KpMetisGraph(ctypes.Structure):
    _fields_ = [
        ("n", ctypes.c_int64),
        ("m", ctypes.c_int64),
        ("row_ptr", ctypes.POINTER(ctypes.c_int64)),
        ("col_idx", ctypes.POINTER(ctypes.c_int64)),
        ("node_w", ctypes.POINTER(ctypes.c_int64)),
        ("edge_w", ctypes.POINTER(ctypes.c_int64)),
        ("error", ctypes.c_char_p),
    ]


def _load():
    global _lib, _lib_failed
    if _lib is not None or _lib_failed:
        return _lib
    if os.environ.get("KAMINPAR_TPU_NO_NATIVE") == "1":
        _lib_failed = True
        return None
    try:
        if (
            not os.path.exists(_SO)
            or os.path.getmtime(_SO) < os.path.getmtime(_SRC)
        ):
            tmp = _SO + f".tmp{os.getpid()}"
            subprocess.run(
                ["g++", "-O3", "-shared", "-fPIC", "-std=c++17", _SRC,
                 "-o", tmp],
                check=True, capture_output=True, timeout=120,
            )
            os.replace(tmp, _SO)  # atomic against a concurrent build
        lib = ctypes.CDLL(_SO)
        lib.kp_parse_metis.argtypes = [ctypes.c_char_p,
                                       ctypes.POINTER(_KpMetisGraph)]
        lib.kp_parse_metis.restype = ctypes.c_int
        lib.kp_free_graph.argtypes = [ctypes.POINTER(_KpMetisGraph)]
        lib.kp_free_graph.restype = None
        _lib = lib
    except Exception:  # noqa: BLE001 — any build/load failure => fallback
        _lib_failed = True
        _lib = None
    return _lib


def native_available() -> bool:
    return _load() is not None


def parse_metis_native(path: str):
    """Parse via the C++ library; returns (row_ptr, col_idx, node_w, edge_w)
    as NumPy arrays (weights None when absent), or None when the native
    layer is unavailable.  Raises ValueError on malformed input."""
    lib = _load()
    if lib is None:
        return None
    if not os.path.isfile(path):
        # keep the exception type toolchain-independent: the NumPy path
        # raises FileNotFoundError from open()
        open(path, "rb").close()
    g = _KpMetisGraph()
    rc = lib.kp_parse_metis(os.fsencode(path), ctypes.byref(g))
    try:
        if rc != 0:
            msg = (g.error or b"parse error").decode()
            raise ValueError(f"{path}: {msg}")
        n, m = g.n, g.m
        row_ptr = np.ctypeslib.as_array(g.row_ptr, shape=(n + 1,)).copy()
        col_idx = (
            np.ctypeslib.as_array(g.col_idx, shape=(m,)).copy()
            if m else np.zeros(0, dtype=np.int64)
        )
        node_w = (
            np.ctypeslib.as_array(g.node_w, shape=(n,)).copy()
            if g.node_w and n else None
        )
        edge_w = (
            np.ctypeslib.as_array(g.edge_w, shape=(m,)).copy()
            if g.edge_w and m else None
        )
        return row_ptr, col_idx, node_w, edge_w
    finally:
        lib.kp_free_graph(ctypes.byref(g))
