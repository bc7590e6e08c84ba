"""ctypes bindings for the native runtime kernels (src/native.cc).

Builds the library on first import, on the machine that runs it: the
file is named after a hash of the source AND this CPU's feature flags
(`-march=native` bakes them in), so a library built from other source
or on another CPU — a working tree copied between machines carries
such files — is never loaded, only rebuilt.  All callers must tolerate
`available() == False` (e.g. no compiler in the environment) and fall
back to pure-python paths.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import pathlib
import platform
import subprocess
import threading
from typing import Optional

import numpy as np

_HERE = pathlib.Path(__file__).resolve().parent
_SRC = _HERE / "src" / "native.cc"
_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False


def _cpu_flags() -> str:
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith("flags"):
                    return line
    except OSError:
        pass
    return platform.machine()


def _so_path() -> pathlib.Path:
    h = hashlib.sha256(_SRC.read_bytes())
    h.update(_cpu_flags().encode())
    return _HERE / f"libceph_tpu_native.{h.hexdigest()[:16]}.so"


def _build(so: pathlib.Path) -> bool:
    # build beside the target and rename: daemons that start together
    # all build, and none may load a half-written file
    tmp = so.with_suffix(f".{os.getpid()}.tmp")
    try:
        subprocess.run(
            ["g++", "-O3", "-march=native", "-fopenmp", "-shared", "-fPIC",
             "-o", str(tmp), str(_SRC)],
            check=True, capture_output=True, timeout=120)
        os.replace(tmp, so)
        return True
    except (OSError, subprocess.SubprocessError):
        tmp.unlink(missing_ok=True)
        return False


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        if not _SRC.exists():
            return None
        so = _so_path()
        if not so.exists() and not _build(so):
            return None  # cannot compile here
        try:
            lib = ctypes.CDLL(str(so))
        except OSError:
            return None
        return _bind(lib)


def _bind(lib: ctypes.CDLL) -> Optional[ctypes.CDLL]:
    global _lib
    try:
        u8p = ctypes.POINTER(ctypes.c_uint8)
        u32p = ctypes.POINTER(ctypes.c_uint32)
        lib.ceph_crc32c.restype = ctypes.c_uint32
        lib.ceph_crc32c.argtypes = [ctypes.c_uint32, u8p, ctypes.c_uint64]
        lib.ceph_crc32c_table.restype = ctypes.c_uint32
        lib.ceph_crc32c_table.argtypes = lib.ceph_crc32c.argtypes
        lib.ceph_crc32c_impl.restype = ctypes.c_char_p
        lib.ceph_crc32c_impl.argtypes = []
        lib.ceph_crc32c_many.restype = None
        lib.ceph_crc32c_many.argtypes = [
            ctypes.POINTER(ctypes.c_char_p),
            ctypes.POINTER(ctypes.c_uint64), ctypes.c_uint64, u32p]
        lib.ceph_rjenkins3.restype = ctypes.c_uint32
        lib.ceph_rjenkins3.argtypes = [ctypes.c_uint32] * 3
        lib.ceph_rjenkins3_batch.argtypes = [
            u32p, ctypes.c_uint32, ctypes.c_uint32, u32p, ctypes.c_uint64]
        lib.ceph_gf_matrix_apply.argtypes = [
            u8p, ctypes.c_int, ctypes.c_int, u8p, u8p, ctypes.c_uint64]
        lib.ceph_gf_matrix_apply_scalar.argtypes = \
            lib.ceph_gf_matrix_apply.argtypes
        lib.ceph_gf_simd_available.restype = ctypes.c_int
        lib.ceph_gf_simd_available.argtypes = []
        lib.ceph_region_xor.argtypes = [u8p, u8p, u8p, ctypes.c_uint64]
        i32p = ctypes.POINTER(ctypes.c_int32)
        i64p = ctypes.POINTER(ctypes.c_int64)
        lib.ceph_straw2_winner_rows.argtypes = [
            i32p, i64p, ctypes.c_int64, ctypes.c_int32, u32p, u32p, i64p,
            i32p]
        lib.ceph_straw2_winner_shared.argtypes = [
            i32p, i64p, ctypes.c_int32, u32p, u32p, ctypes.c_int64, i64p,
            i32p]
        lib.ceph_straw2_winner_rows_indexed.argtypes = [
            i32p, i64p, i64p, ctypes.c_int64, ctypes.c_int32, u32p,
            u32p, i64p, i32p]
        lib.ceph_xxh32.restype = ctypes.c_uint32
        lib.ceph_xxh32.argtypes = [u8p, ctypes.c_uint64,
                                   ctypes.c_uint32]
        lib.ceph_xxh64.restype = ctypes.c_uint64
        lib.ceph_xxh64.argtypes = [u8p, ctypes.c_uint64,
                                   ctypes.c_uint64]
    except AttributeError:
        # a symbol the bindings expect is missing: degrade to
        # unavailable, never raise out of _load — callers rely on
        # available() -> False for the pure-python paths
        return None
    _lib = lib
    return _lib


def available() -> bool:
    return _load() is not None


def _u8p(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))


def _crc32c(fn: str, data, crc: int) -> int:
    lib = _load()
    if lib is None:
        raise RuntimeError("native crc32c unavailable (check available())")
    buf = np.frombuffer(data, np.uint8)
    return int(getattr(lib, fn)(crc, _u8p(buf), buf.size))


def crc32c(data, crc: int = 0) -> int:
    """Castagnoli CRC (reference common/crc32c.h semantics) of any
    contiguous buffer, on the path crc32c_impl() names."""
    return _crc32c("ceph_crc32c", data, crc)


def crc32c_many(blobs) -> list:
    """crc32c of each of `blobs` (`bytes` objects), all in ONE call into
    the library: the GIL is given up once for the lot.  A thread beside
    a busy event loop pays for every time it has to win the GIL back,
    so six digests cost it one such wait, not six."""
    lib = _load()
    if lib is None:
        raise RuntimeError("native crc32c unavailable (check available())")
    n = len(blobs)
    out = (ctypes.c_uint32 * n)()
    lib.ceph_crc32c_many((ctypes.c_char_p * n)(*blobs),
                         (ctypes.c_uint64 * n)(*map(len, blobs)), n, out)
    return list(out)


def crc32c_table(data, crc: int = 0) -> int:
    """The same digest by the slicing-by-8 table: crc32c's fallback
    where the CPU lacks the instruction, and the tests' reference."""
    return _crc32c("ceph_crc32c_table", data, crc)


def crc32c_impl() -> str:
    """Which path crc32c takes, decided by the build ("sse42x3": the
    CPU's CRC32C instruction on three interleaved streams; "table")."""
    lib = _load()
    if lib is None:
        raise RuntimeError("native crc32c unavailable (check available())")
    return lib.ceph_crc32c_impl().decode()


def xxh32(data: bytes, seed: int = 0) -> int:
    lib = _load()
    if lib is None:
        raise RuntimeError("native xxh32 unavailable (check available())")
    buf = np.frombuffer(data, np.uint8)
    return int(lib.ceph_xxh32(_u8p(buf), buf.size, seed & 0xFFFFFFFF))


def xxh64(data: bytes, seed: int = 0) -> int:
    lib = _load()
    if lib is None:
        raise RuntimeError("native xxh64 unavailable (check available())")
    buf = np.frombuffer(data, np.uint8)
    return int(lib.ceph_xxh64(_u8p(buf), buf.size,
                              seed & 0xFFFFFFFFFFFFFFFF))


def rjenkins3(a: int, b: int, c: int) -> int:
    lib = _load()
    assert lib is not None
    return int(lib.ceph_rjenkins3(a & 0xFFFFFFFF, b & 0xFFFFFFFF,
                                  c & 0xFFFFFFFF))


def rjenkins3_batch(a: np.ndarray, b: int, c: int) -> np.ndarray:
    """Vector hash32_3(a[i], b, c) — host-side placement fallback hot loop."""
    lib = _load()
    assert lib is not None
    a = np.ascontiguousarray(a, np.uint32)
    out = np.empty_like(a)
    u32p = ctypes.POINTER(ctypes.c_uint32)
    lib.ceph_rjenkins3_batch(a.ctypes.data_as(u32p), b & 0xFFFFFFFF,
                             c & 0xFFFFFFFF, out.ctypes.data_as(u32p),
                             a.size)
    return out


def gf_matrix_apply(mat: np.ndarray, chunks: np.ndarray,
                    force_scalar: bool = False) -> np.ndarray:
    """CPU-baseline GF(2^8) matrix apply: out[r, L] = mat @ chunks.

    Dispatches to the GFNI/AVX-512 kernel when the host supports it
    (the isa-l-class SIMD baseline); force_scalar pins the jerasure-style
    table sweep for comparison."""
    lib = _load()
    assert lib is not None, "native library unavailable"
    mat = np.ascontiguousarray(mat, np.uint8)
    chunks = np.ascontiguousarray(chunks, np.uint8)
    r, k = mat.shape
    assert chunks.shape[0] == k
    out = np.empty((r, chunks.shape[1]), np.uint8)
    fn = (lib.ceph_gf_matrix_apply_scalar if force_scalar
          else lib.ceph_gf_matrix_apply)
    fn(_u8p(mat), r, k, _u8p(chunks), _u8p(out), chunks.shape[1])
    return out


def gf_simd_available() -> bool:
    """True when gf_matrix_apply runs the GFNI/AVX-512 SIMD kernel."""
    lib = _load()
    return bool(lib is not None and lib.ceph_gf_simd_available())


def region_xor(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    lib = _load()
    assert lib is not None
    a = np.ascontiguousarray(a, np.uint8)
    b = np.ascontiguousarray(b, np.uint8)
    out = np.empty_like(a)
    lib.ceph_region_xor(_u8p(a), _u8p(b), _u8p(out), a.size)
    return out


def straw2_winner_rows(items: np.ndarray, weights: np.ndarray,
                       xs: np.ndarray, rs: np.ndarray,
                       ln_tab: np.ndarray) -> np.ndarray:
    """Row-wise batched straw2 argmax (the CPU engine of the batched
    placement kernel, ops/crush_kernel.py).  items/weights [X, I],
    xs/rs [X], ln_tab [65536] int64 -> winning index [X]."""
    lib = _load()
    assert lib is not None
    items = np.ascontiguousarray(items, np.int32)
    weights = np.ascontiguousarray(weights, np.int64)
    xs = np.ascontiguousarray(xs, np.uint32)
    rs = np.ascontiguousarray(rs, np.uint32)
    ln_tab = np.ascontiguousarray(ln_tab, np.int64)
    X, I = items.shape
    out = np.empty(X, np.int32)
    i32p = ctypes.POINTER(ctypes.c_int32)
    i64p = ctypes.POINTER(ctypes.c_int64)
    u32p = ctypes.POINTER(ctypes.c_uint32)
    lib.ceph_straw2_winner_rows(
        items.ctypes.data_as(i32p), weights.ctypes.data_as(i64p),
        X, I, xs.ctypes.data_as(u32p), rs.ctypes.data_as(u32p),
        ln_tab.ctypes.data_as(i64p), out.ctypes.data_as(i32p))
    return out.astype(np.int64)


def straw2_winner_rows_indexed(items_tab: np.ndarray,
                               weights_tab: np.ndarray,
                               rows: np.ndarray, xs: np.ndarray,
                               rs: np.ndarray,
                               ln_tab: np.ndarray) -> np.ndarray:
    """Level-table straw2 argmax: items/weights [N, I] shared table,
    rows [X] lane->row indices -> chosen ITEM ids [X].  Skips the
    [X, I] gather the plain rows kernel needs (multi-level descent
    hot path, ops/crush_kernel._descend)."""
    lib = _load()
    assert lib is not None
    assert items_tab.dtype == np.int32 and items_tab.flags.c_contiguous
    assert weights_tab.dtype == np.int64 \
        and weights_tab.flags.c_contiguous
    rows = np.ascontiguousarray(rows, np.int64)
    xs = np.ascontiguousarray(xs, np.uint32)
    rs = np.ascontiguousarray(rs, np.uint32)
    ln_tab = np.ascontiguousarray(ln_tab, np.int64)
    _, I = items_tab.shape
    X = len(rows)
    out = np.empty(X, np.int32)
    i32p = ctypes.POINTER(ctypes.c_int32)
    i64p = ctypes.POINTER(ctypes.c_int64)
    u32p = ctypes.POINTER(ctypes.c_uint32)
    lib.ceph_straw2_winner_rows_indexed(
        items_tab.ctypes.data_as(i32p),
        weights_tab.ctypes.data_as(i64p),
        rows.ctypes.data_as(i64p), X, I,
        xs.ctypes.data_as(u32p), rs.ctypes.data_as(u32p),
        ln_tab.ctypes.data_as(i64p), out.ctypes.data_as(i32p))
    return out.astype(np.int64)


def straw2_winner_shared(items: np.ndarray, weights: np.ndarray,
                         xs: np.ndarray, rs: np.ndarray,
                         ln_tab: np.ndarray) -> np.ndarray:
    """Shared-bucket batched straw2 argmax: items/weights [I] drawn by
    every lane (root-bucket case) — no [X, I] materialization."""
    lib = _load()
    assert lib is not None
    items = np.ascontiguousarray(items, np.int32)
    weights = np.ascontiguousarray(weights, np.int64)
    xs = np.ascontiguousarray(xs, np.uint32)
    rs = np.ascontiguousarray(rs, np.uint32)
    ln_tab = np.ascontiguousarray(ln_tab, np.int64)
    out = np.empty(len(xs), np.int32)
    i32p = ctypes.POINTER(ctypes.c_int32)
    i64p = ctypes.POINTER(ctypes.c_int64)
    u32p = ctypes.POINTER(ctypes.c_uint32)
    lib.ceph_straw2_winner_shared(
        items.ctypes.data_as(i32p), weights.ctypes.data_as(i64p),
        items.size, xs.ctypes.data_as(u32p), rs.ctypes.data_as(u32p),
        len(xs), ln_tab.ctypes.data_as(i64p), out.ctypes.data_as(i32p))
    return out.astype(np.int64)
