"""ctypes bindings for the native C++ host data layer (``native/``).

The TPU compute path is XLA/Pallas; the runtime *around* it — synthetic
graph generation, matrix-market IO, and the bucket sorts behind nonzero
redistribution — is native C++/OpenMP, matching the reference's
native-host architecture (CombBLAS IO + R-mat at
`/root/reference/SpmatLocal.hpp:467-533`, Alltoallv redistribution +
parallel sort at `SpmatLocal.hpp:389-462`).

The library is built lazily with the repo's ``native/Makefile`` on first
use; every entry point has a numpy fallback so the package works without a
toolchain (``available()`` reports which path is active, and the
``HNH_NO_NATIVE=1`` env var forces the fallback).
"""

from __future__ import annotations

import ctypes
import os
import pathlib
import subprocess
import threading

import numpy as np

_LIB_DIR = pathlib.Path(__file__).parent / "_native"
_LIB_PATH = _LIB_DIR / "libhnh_native.so"
_SRC_DIR = pathlib.Path(__file__).parent.parent / "native"

_lock = threading.Lock()
_lib = None
_tried = False
#: Why the library is not loaded (build or load failure), else None.
_error: str | None = None

_i64p = np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS")
_f64p = np.ctypeslib.ndpointer(np.float64, flags="C_CONTIGUOUS")


def _is_fresh() -> bool:
    src = _SRC_DIR / "hnh_native.cpp"
    try:
        return (
            _LIB_PATH.exists()
            and (not src.exists() or src.stat().st_mtime <= _LIB_PATH.stat().st_mtime)
        )
    except OSError:
        return False


def _try_build() -> bool:
    """Build (or rebuild a stale) library; concurrency-safe.

    Compiles to a per-process temp name and atomically renames into place,
    so parallel imports (pytest-xdist, multi-process launches) never dlopen
    a half-written .so or clobber each other's compile.
    """
    if _is_fresh():
        return True
    global _error
    if not (_SRC_DIR / "Makefile").exists():
        _error = f"no {_SRC_DIR / 'Makefile'}"
        return False
    tmp = _LIB_DIR / f"libhnh_native.build{os.getpid()}.so"
    try:
        subprocess.run(
            ["make", "-C", str(_SRC_DIR), f"OUT={tmp}"],
            check=True,
            capture_output=True,
            timeout=120,
        )
        os.replace(tmp, _LIB_PATH)
    except subprocess.CalledProcessError as e:
        tmp.unlink(missing_ok=True)
        tail = (e.stderr or b"").decode(errors="replace").strip()
        _error = f"make failed (rc={e.returncode}): {tail[-500:]}"
        return False
    except (subprocess.SubprocessError, OSError) as e:
        tmp.unlink(missing_ok=True)
        _error = f"make failed: {type(e).__name__}: {e}"
        return False
    return _LIB_PATH.exists()


def _load():
    global _lib, _tried, _error
    with _lock:
        if _tried:
            return _lib
        _tried = True
        if os.environ.get("HNH_NO_NATIVE") == "1":
            return None
        # Always run make when the source tree is present: it is a no-op
        # for a fresh build and rebuilds stale binaries after source edits.
        if not _try_build() and not _LIB_PATH.exists():
            return None
        try:
            lib = ctypes.CDLL(str(_LIB_PATH))
        except OSError as e:
            _error = f"load failed: {e}"
            return None
        lib.hnh_rmat.argtypes = [
            ctypes.c_int64, ctypes.c_int64,
            ctypes.c_double, ctypes.c_double, ctypes.c_double, ctypes.c_double,
            ctypes.c_uint64, _i64p, _i64p,
        ]
        lib.hnh_bucket_sort.argtypes = [
            _i64p, ctypes.c_int64, ctypes.c_int64, _i64p, _i64p,
        ]
        lib.hnh_bucket_sort.restype = ctypes.c_int
        lib.hnh_mtx_header.argtypes = [
            ctypes.c_char_p,
            ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_int), ctypes.POINTER(ctypes.c_int),
        ]
        lib.hnh_mtx_header.restype = ctypes.c_int
        lib.hnh_mtx_read.argtypes = [
            ctypes.c_char_p, ctypes.c_int64, ctypes.c_int, _i64p, _i64p, _f64p,
        ]
        lib.hnh_mtx_read.restype = ctypes.c_int64
        lib.hnh_mtx_write.argtypes = [
            ctypes.c_char_p, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
            _i64p, _i64p, _f64p,
        ]
        lib.hnh_mtx_write.restype = ctypes.c_int64
        lib.hnh_parse_triplets.argtypes = [
            ctypes.c_char_p, ctypes.c_int64, ctypes.c_int, ctypes.c_int64,
            _i64p, _i64p, _f64p, ctypes.POINTER(ctypes.c_int64),
        ]
        lib.hnh_parse_triplets.restype = ctypes.c_int64
        lib.hnh_num_threads.restype = ctypes.c_int
        _lib = lib
        return _lib


def available() -> bool:
    return _load() is not None


def build_error() -> str | None:
    """Why the native library is not in use (None when it is, or when
    ``HNH_NO_NATIVE=1`` turned it off)."""
    _load()
    return _error


# --------------------------------------------------------------------- #
# R-mat generation
# --------------------------------------------------------------------- #

def rmat_edges(log_m, n_edges, a, b, c, d, seed):
    """Generate R-mat edge endpoints; native when available.

    The native path uses counter-based splitmix64 streams (deterministic
    for a given seed, independent of thread count); the numpy fallback uses
    a different RNG, so cross-path runs agree statistically, not bitwise.
    """
    lib = _load()
    if lib is not None:
        rows = np.empty(n_edges, np.int64)
        cols = np.empty(n_edges, np.int64)
        lib.hnh_rmat(log_m, n_edges, a, b, c, d, np.uint64(seed), rows, cols)
        return rows, cols
    rng = np.random.default_rng(seed)
    rows = np.zeros(n_edges, dtype=np.int64)
    cols = np.zeros(n_edges, dtype=np.int64)
    ab = a + b
    top = b / ab if ab > 0 else 0.0
    bot = d / (c + d) if (c + d) > 0 else 0.0
    for _ in range(log_m):
        rbit = (rng.random(n_edges) >= ab).astype(np.int64)
        cprob = np.where(rbit == 0, top, bot)
        cbit = (rng.random(n_edges) < cprob).astype(np.int64)
        rows = (rows << 1) | rbit
        cols = (cols << 1) | cbit
    return rows, cols


# --------------------------------------------------------------------- #
# Stable bucket sort (the redistribution/chunking workhorse)
# --------------------------------------------------------------------- #

def bucket_sort(keys: np.ndarray, n_buckets: int):
    """Return ``(counts[n_buckets], order[n])`` = stable argsort by bucket.

    Equivalent to ``np.argsort(keys, kind="stable")`` +
    ``np.bincount(keys, minlength=n_buckets)`` but O(n) and parallel in the
    native path.
    """
    keys = np.ascontiguousarray(keys, dtype=np.int64)
    if keys.size and (keys.min() < 0 or keys.max() >= n_buckets):
        # Match the numpy path's behavior (np.bincount raises); the native
        # histogram would write out of bounds on bad keys.
        raise ValueError(
            f"bucket keys out of range [0, {n_buckets}): "
            f"min={keys.min()}, max={keys.max()}"
        )
    lib = _load()
    if lib is not None and keys.size:
        counts = np.empty(n_buckets, np.int64)
        order = np.empty(keys.size, np.int64)
        rc = lib.hnh_bucket_sort(keys, keys.size, n_buckets, counts, order)
        if rc == 0:
            return counts, order
        # Histogram allocation failed (astronomical n_buckets): fall through
        # to the numpy path rather than returning uninitialized buffers.
    order = np.argsort(keys, kind="stable")
    counts = np.bincount(keys, minlength=n_buckets).astype(np.int64)
    return counts, order


# --------------------------------------------------------------------- #
# Matrix-market IO
# --------------------------------------------------------------------- #

def _mtx_read_scipy(path: str):
    import scipy.io

    coo = scipy.io.mmread(path).tocoo()
    return (
        coo.row.astype(np.int64), coo.col.astype(np.int64),
        coo.data.astype(np.float64), int(coo.shape[0]), int(coo.shape[1]),
    )


def mtx_read(path: str):
    """Read a coordinate .mtx file -> (rows, cols, vals, M, N).

    Symmetric headers are expanded (mirror entries negated for
    skew-symmetric); complex/dense files fall back to the scipy reader."""
    lib = _load()
    if lib is None:
        return _mtx_read_scipy(path)
    M = ctypes.c_int64()
    N = ctypes.c_int64()
    nnz = ctypes.c_int64()
    sym = ctypes.c_int()
    pat = ctypes.c_int()
    rc = lib.hnh_mtx_header(
        path.encode(), ctypes.byref(M), ctypes.byref(N), ctypes.byref(nnz),
        ctypes.byref(sym), ctypes.byref(pat),
    )
    if rc in (-4, -6):  # dense 'array' / complex: not handled natively
        return _mtx_read_scipy(path)
    if rc != 0:
        raise IOError(f"failed to parse matrix-market header of {path} (rc={rc})")
    rows = np.empty(nnz.value, np.int64)
    cols = np.empty(nnz.value, np.int64)
    vals = np.empty(nnz.value, np.float64)
    got = lib.hnh_mtx_read(path.encode(), nnz.value, pat.value, rows, cols, vals)
    if got != nnz.value:
        raise IOError(f"{path}: expected {nnz.value} entries, parsed {got}")
    if sym.value:
        off = rows != cols
        mirror_r, mirror_c = cols[off], rows[off]
        mirror_v = -vals[off] if sym.value == 2 else vals[off]
        rows = np.concatenate([rows, mirror_r])
        cols = np.concatenate([cols, mirror_c])
        vals = np.concatenate([vals, mirror_v])
    return rows, cols, vals, M.value, N.value


def parse_triplets(buf: bytes, pattern: bool = False):
    """Parse an in-memory chunk of matrix-market data lines ->
    ``(rows_1based-1, cols-1, vals)`` — or None when the native layer is
    unavailable (the caller falls back to a numpy text reader).

    The ctypes call releases the GIL, which is what makes the
    partitioned loader's thread-pool chunk parse genuinely parallel;
    ``strtol``/``strtod`` produce the same correctly-rounded doubles as
    numpy's tokenizer, so the two paths are bit-identical on valid
    files — and strictness-identical on corrupt ones: a non-blank line
    that does not parse raises ``ValueError`` here exactly where
    ``np.loadtxt`` would in the fallback, instead of silently dropping
    entries.
    """
    import ctypes as _ct

    lib = _load()
    if lib is None:
        return None
    cap = buf.count(b"\n") + 1
    rows = np.empty(cap, np.int64)
    cols = np.empty(cap, np.int64)
    vals = np.empty(cap, np.float64)
    n_bad = _ct.c_int64(0)
    n = lib.hnh_parse_triplets(
        buf, len(buf), 1 if pattern else 0, cap, rows, cols, vals,
        _ct.byref(n_bad),
    )
    if n < 0:
        return None
    if n_bad.value:
        raise ValueError(
            f"{n_bad.value} malformed matrix-market data line(s) in chunk"
        )
    return rows[:n], cols[:n], vals[:n]


def mtx_write(path: str, rows, cols, vals, M: int, N: int) -> None:
    lib = _load()
    if lib is None:
        import scipy.io
        import scipy.sparse as sp

        scipy.io.mmwrite(
            path, sp.coo_matrix((vals, (rows, cols)), shape=(M, N))
        )
        return
    rows = np.ascontiguousarray(rows, np.int64)
    cols = np.ascontiguousarray(cols, np.int64)
    vals = np.ascontiguousarray(vals, np.float64)
    if lib.hnh_mtx_write(path.encode(), M, N, rows.size, rows, cols, vals) < 0:
        raise IOError(f"failed to write {path}")
