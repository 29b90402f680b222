"""Native (C++) kernels, loaded via ctypes with graceful fallback.

The shared library is compiled on first use with the system g++ (cached
next to the source, named by a hash of the source, so a library is only
ever loaded for the exact source it was built from) — no pybind11 or
build step in the critical path; environments without a compiler run
the pure-Python implementations and log that they do (`decoder_kind()`
says which is in use). Ref: SURVEY.md §7 — the reference's storage-side
hot loops live in Rust TiKV; this is our C++ equivalent layer.
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
import threading
from pathlib import Path

import numpy as np

__all__ = ["lib", "decoder_kind", "decode_rows_native", "scan_rows_native",
           "NATIVE_KIND_INT", "NATIVE_KIND_FLOAT", "NATIVE_KIND_DECIMAL",
           "NATIVE_KIND_HANDLE", "NATIVE_KIND_BYTES"]

NATIVE_KIND_INT = 0
NATIVE_KIND_FLOAT = 1
NATIVE_KIND_DECIMAL = 2
NATIVE_KIND_HANDLE = 3
NATIVE_KIND_BYTES = 4

_lock = threading.Lock()
_lib = None
_tried = False


def _compile(name: str) -> ctypes.CDLL | None:
    """Build native/<name>.cc into _build/<name>-<source hash>.so and
    load it; None (logged) when there is no compiler or the build or
    load fails."""
    src = Path(__file__).parent / f"{name}.cc"
    build_dir = Path(__file__).parent / "_build"
    digest = hashlib.sha256(src.read_bytes()).hexdigest()[:16]
    so = build_dir / f"{name}-{digest}.so"
    try:
        if not so.exists():
            build_dir.mkdir(exist_ok=True)
            tmp = so.with_suffix(".so.tmp%d" % os.getpid())
            subprocess.run(
                ["g++", "-O3", "-shared", "-fPIC", "-std=c++17",
                 "-o", str(tmp), str(src)],
                check=True, capture_output=True, timeout=120)
            os.replace(tmp, so)
        return ctypes.CDLL(str(so))
    except (OSError, subprocess.SubprocessError) as e:
        logging.getLogger("tidb_tpu.native").warning(
            "native %s unavailable (%s); using the pure-Python path",
            name, e)
        return None


def _build() -> ctypes.CDLL | None:
    cdll = _compile("codec")
    if cdll is None:
        return None
    cdll.decode_rows.restype = ctypes.c_int64
    cdll.decode_rows.argtypes = [
        ctypes.c_char_p, ctypes.POINTER(ctypes.c_int64),
        ctypes.c_char_p, ctypes.POINTER(ctypes.c_int64),
        ctypes.c_int64, ctypes.c_int32,
        ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_uint8),
        ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_uint8),
        ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_double),
        ctypes.POINTER(ctypes.c_void_p), ctypes.POINTER(ctypes.c_void_p),
        ctypes.c_void_p,
    ]
    return cdll


def _build_loadscan() -> ctypes.CDLL | None:
    cdll = _compile("loadscan")
    if cdll is None:
        return None
    cdll.scan_rows.restype = ctypes.c_int64
    P64 = ctypes.POINTER(ctypes.c_int64)
    P8 = ctypes.POINTER(ctypes.c_uint8)
    cdll.scan_rows.argtypes = [
        ctypes.c_char_p, ctypes.c_int64,
        ctypes.c_uint8, ctypes.c_uint8, ctypes.c_int32, ctypes.c_int32,
        ctypes.c_int64, ctypes.c_int32,
        P64, P64, P8, P64, ctypes.c_int64, ctypes.c_int64, P64, P64,
    ]
    return cdll


_scan_lock = threading.Lock()
_scan_lib = None
_scan_tried = False


def _loadscan_lib() -> ctypes.CDLL | None:
    global _scan_lib, _scan_tried
    if _scan_tried:
        return _scan_lib
    with _scan_lock:
        if not _scan_tried:
            _scan_lib = _build_loadscan()
            _scan_tried = True
    return _scan_lib


def scan_rows_native(data: bytes, ft: bytes, lt: bytes, enc: bytes,
                     esc: bytes, ignore_lines: int,
                     final_chunk: bool = True):
    """Scan LOAD DATA text into field spans.

    -> (consumed_bytes, rowoff int64[nr+1], fstart, fend, fflags) or
    None when the native scanner is unavailable. consumed < len(data)
    means the caller must run the general scanner on the remainder."""
    cdll = _loadscan_lib()
    if cdll is None:
        return None
    n = len(data)
    # upper bounds: every separator byte could open a field/row
    max_fields = data.count(ft) + data.count(lt) + 2
    max_rows = data.count(lt) + 2
    fstart = np.empty(max_fields, dtype=np.int64)
    fend = np.empty(max_fields, dtype=np.int64)
    fflags = np.empty(max_fields, dtype=np.uint8)
    rowoff = np.zeros(max_rows + 1, dtype=np.int64)
    out = np.zeros(2, dtype=np.int64)
    P64 = ctypes.POINTER(ctypes.c_int64)
    P8 = ctypes.POINTER(ctypes.c_uint8)
    consumed = cdll.scan_rows(
        data, n, ft[0], lt[0],
        enc[0] if enc else -1, esc[0] if esc else -1,
        ignore_lines, 1 if final_chunk else 0,
        fstart.ctypes.data_as(P64), fend.ctypes.data_as(P64),
        fflags.ctypes.data_as(P8), rowoff.ctypes.data_as(P64),
        max_fields, max_rows,
        out[0:].ctypes.data_as(P64), out[1:].ctypes.data_as(P64))
    nr, nf = int(out[0]), int(out[1])
    return (int(consumed), rowoff[:nr + 1], fstart[:nf], fend[:nf],
            fflags[:nf])


def lib() -> ctypes.CDLL | None:
    """The native library, or None when unavailable."""
    global _lib, _tried
    if _tried:
        return _lib
    with _lock:
        if not _tried:
            _lib = _build()
            _tried = True
    return _lib


def decoder_kind() -> str:
    """Which row decoder serves `table.kvrows_to_chunk`: "native" (the
    compiled codec.cc) or "python" (no compiler / build failed)."""
    return "native" if lib() is not None else "python"


def decode_rows_native(kvrows, col_specs):
    """Batch-decode record (key, value) pairs into columnar arrays.

    col_specs: list of (col_id, kind, frac, default_valid, default_value)
    — kind NATIVE_KIND_*; for HANDLE the id/default are ignored, and a
    BYTES column's default value is the caller's to place.
    Returns (datas, valids, strbuf): numpy arrays per column — int64 or
    float64 values, and for a BYTES column an int64[2, n] of each row's
    [start, end) in `strbuf`, the batch's un-stuffed `bytes` (an empty
    range for NULL, -1 where the row lacks the column and its default is
    not NULL) — or None when the native path is unavailable or declined
    the input (caller uses the Python decoder).
    """
    cdll = lib()
    if cdll is None:
        return None
    n = len(kvrows)
    ks, vs = zip(*kvrows) if n else ((), ())
    keys = b"".join(ks)
    values = b"".join(vs)
    key_offs = np.zeros(n + 1, dtype=np.int64)
    val_offs = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(np.fromiter(map(len, ks), dtype=np.int64, count=n),
              out=key_offs[1:])
    np.cumsum(np.fromiter(map(len, vs), dtype=np.int64, count=n),
              out=val_offs[1:])

    ncols = len(col_specs)
    col_ids = np.array([s[0] for s in col_specs], dtype=np.int64)
    col_kind = np.array([s[1] for s in col_specs], dtype=np.uint8)
    col_frac = np.array([max(0, s[2]) for s in col_specs], dtype=np.int32)
    def_valid = np.array([1 if s[3] else 0 for s in col_specs],
                         dtype=np.uint8)
    def_int = np.zeros(ncols, dtype=np.int64)
    def_float = np.zeros(ncols, dtype=np.float64)
    for i, s in enumerate(col_specs):
        if s[3] and s[4] is not None:
            if s[1] == NATIVE_KIND_FLOAT:
                def_float[i] = float(s[4])
            elif s[1] != NATIVE_KIND_BYTES:
                def_int[i] = int(s[4])
        elif s[3] and s[4] is None:
            def_valid[i] = 0   # default is NULL

    datas = []
    valids = []
    out_ptrs = (ctypes.c_void_p * ncols)()
    valid_ptrs = (ctypes.c_void_p * ncols)()
    for i, s in enumerate(col_specs):
        if s[1] == NATIVE_KIND_BYTES:
            d = np.zeros((2, n), dtype=np.int64)
        else:
            d = np.zeros(n, dtype=np.float64 if s[1] == NATIVE_KIND_FLOAT
                         else np.int64)
        m = np.zeros(n, dtype=np.uint8)
        datas.append(d)
        valids.append(m)
        out_ptrs[i] = d.ctypes.data_as(ctypes.c_void_p)
        valid_ptrs[i] = m.ctypes.data_as(ctypes.c_void_p)
    # un-stuffing never grows a datum: the values' length holds every
    # BYTES column of the batch
    strbuf = np.empty(len(values) if NATIVE_KIND_BYTES in col_kind else 0,
                      dtype=np.uint8)

    used = cdll.decode_rows(
        values, val_offs.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        keys, key_offs.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        n, ncols,
        col_ids.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        col_kind.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        col_frac.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        def_valid.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        def_int.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        def_float.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
        out_ptrs, valid_ptrs, strbuf.ctypes.data_as(ctypes.c_void_p))
    if used < 0:
        return None
    return (datas, [m.astype(bool) for m in valids],
            strbuf[:used].tobytes())
