#
# ctypes bindings for the native host runtime (native/ -> libsrml_native.so).
#
# The reference loads its native layer via JNI (JniRAPIDSML.java:26-62:
# extract .so, System.load, declare natives); here the same role is played by
# ctypes over a C API (no pybind11 in the image).  The library is OPTIONAL
# and built outside git (native/build/ is ignored): where no .so exists, or
# SRML_NATIVE=0, `lib()` returns None and the numpy routes ARE the path
# (`available()` says which ran).  A .so that exists but does not load, or
# lacks a declared symbol (a stale build), is an error — never a quiet skip.
#
# Build: `make -C native` or `cmake -S native -B native/build && cmake --build
# native/build`.  Override discovery with SRML_NATIVE_LIB=/path/to/.so.
#

from __future__ import annotations

import ctypes
import os
import threading
from typing import List, Optional, Tuple

import numpy as np

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_lib_tried = False

_c_float_p = ctypes.POINTER(ctypes.c_float)
_c_double_p = ctypes.POINTER(ctypes.c_double)
_c_int64_p = ctypes.POINTER(ctypes.c_int64)


def _candidate_paths() -> List[str]:
    override = os.environ.get("SRML_NATIVE_LIB")
    if override:
        return [override]
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return [
        os.path.join(root, "native", "build", "libsrml_native.so"),
        os.path.join(os.path.dirname(os.path.abspath(__file__)), "libsrml_native.so"),
    ]


def _declare(lib: ctypes.CDLL) -> None:
    lib.srml_version.restype = ctypes.c_char_p
    lib.srml_hardware_threads.restype = ctypes.c_int
    lib.srml_buf_alloc.restype = ctypes.c_void_p
    lib.srml_buf_alloc.argtypes = [ctypes.c_size_t]
    lib.srml_buf_free.argtypes = [ctypes.c_void_p]
    lib.srml_buf_trim.argtypes = []
    lib.srml_buf_cached_bytes.restype = ctypes.c_size_t
    lib.srml_concat_f32.restype = ctypes.c_int
    lib.srml_concat_f32.argtypes = [
        ctypes.POINTER(_c_float_p), _c_int64_p, ctypes.c_int, ctypes.c_int64, _c_float_p,
    ]
    lib.srml_concat_f64_to_f32.restype = ctypes.c_int
    lib.srml_concat_f64_to_f32.argtypes = [
        ctypes.POINTER(_c_double_p), _c_int64_p, ctypes.c_int, ctypes.c_int64, _c_float_p,
    ]
    lib.srml_concat_f64.restype = ctypes.c_int
    lib.srml_concat_f64.argtypes = [
        ctypes.POINTER(_c_double_p), _c_int64_p, ctypes.c_int, ctypes.c_int64, _c_double_p,
    ]
    lib.srml_csv_count_rows.restype = ctypes.c_int64
    lib.srml_csv_count_rows.argtypes = [ctypes.c_char_p]
    lib.srml_load_csv_f32.restype = ctypes.c_int64
    lib.srml_load_csv_f32.argtypes = [
        ctypes.c_char_p, ctypes.c_int64, ctypes.c_int64, ctypes.c_int, ctypes.c_char, _c_float_p,
    ]
    lib.srml_cov_accumulate.restype = ctypes.c_int
    lib.srml_cov_accumulate.argtypes = [
        _c_double_p, ctypes.c_int64, ctypes.c_int64, _c_double_p, _c_double_p,
    ]
    lib.srml_cov_finalize.restype = ctypes.c_int
    lib.srml_cov_finalize.argtypes = [
        _c_double_p, _c_double_p, ctypes.c_int64, ctypes.c_int64, _c_double_p,
    ]
    lib.srml_eigh_jacobi.restype = ctypes.c_int
    lib.srml_eigh_jacobi.argtypes = [_c_double_p, ctypes.c_int64, _c_double_p, _c_double_p]
    lib.srml_topk_select.restype = ctypes.c_int
    lib.srml_topk_select.argtypes = [
        _c_float_p, ctypes.c_int64, ctypes.c_int64, ctypes.c_int, ctypes.c_int64,
        _c_float_p, _c_int64_p,
    ]
    lib.srml_topk_merge.restype = ctypes.c_int
    lib.srml_topk_merge.argtypes = [
        _c_float_p, _c_int64_p, _c_float_p, _c_int64_p, ctypes.c_int64, ctypes.c_int,
    ]


def lib() -> Optional[ctypes.CDLL]:
    """The loaded native library, or None when not built/disabled.  Raises
    when a library file is present but unloadable or stale."""
    global _lib, _lib_tried
    if os.environ.get("SRML_NATIVE", "1") == "0":
        return None
    with _lock:
        if _lib_tried:
            return _lib
        for path in _candidate_paths():
            if os.path.exists(path):
                try:
                    candidate = ctypes.CDLL(path)
                    _declare(candidate)
                except (OSError, AttributeError) as exc:
                    raise RuntimeError(
                        f"native library {path} exists but is unusable "
                        f"({exc}); rebuild it (`make -C native`) or remove it"
                    ) from exc
                _lib = candidate
                break
        _lib_tried = True
        return _lib


def available() -> bool:
    return lib() is not None


def version() -> Optional[str]:
    l = lib()
    return l.srml_version().decode() if l else None


# ---------------------------------------------------------------------------
# numpy-facing wrappers (each has a pure-numpy fallback used when lib()=None)
# ---------------------------------------------------------------------------


def concat_rows(parts: List[np.ndarray], dtype: np.dtype) -> np.ndarray:
    """Concatenate 2-D row blocks into one C-order matrix of `dtype`,
    converting f64->f32 on the fly when needed (threaded in native code)."""
    dtype = np.dtype(dtype)
    l = lib()
    if not parts:
        return np.zeros((0, 0), dtype=dtype)
    cols = parts[0].shape[1]
    total = sum(p.shape[0] for p in parts)
    src_dtypes = {p.dtype for p in parts}
    if (
        l is None
        or dtype not in (np.float32, np.float64)
        or len(src_dtypes) != 1
        or any(not p.flags.c_contiguous for p in parts)
        or any(p.shape[1] != cols for p in parts)
    ):
        out = np.empty((total, cols), dtype=dtype, order="C")
        off = 0
        for p in parts:
            out[off : off + p.shape[0]] = p
            off += p.shape[0]
        return out
    src_dtype = src_dtypes.pop()
    dst = np.empty((total, cols), dtype=dtype, order="C")
    rows = np.array([p.shape[0] for p in parts], dtype=np.int64)
    n = len(parts)
    if src_dtype == np.float32 and dtype == np.float32:
        src_ptr_t, dst_ptr_t, fn = _c_float_p, _c_float_p, l.srml_concat_f32
    elif src_dtype == np.float64 and dtype == np.float32:
        src_ptr_t, dst_ptr_t, fn = _c_double_p, _c_float_p, l.srml_concat_f64_to_f32
    elif src_dtype == np.float64 and dtype == np.float64:
        src_ptr_t, dst_ptr_t, fn = _c_double_p, _c_double_p, l.srml_concat_f64
    else:  # f32 -> f64: rare; numpy handles it fine
        return np.concatenate(parts).astype(dtype, order="C")
    srcs = (src_ptr_t * n)(*[p.ctypes.data_as(src_ptr_t) for p in parts])
    rc = fn(srcs, rows.ctypes.data_as(_c_int64_p), n, cols, dst.ctypes.data_as(dst_ptr_t))
    if rc != 0:
        raise RuntimeError(f"srml_concat failed: {rc}")
    return dst


def csv_count_rows(path: str) -> int:
    """Rows in a text file, counted natively (fallback: Python iteration)."""
    l = lib()
    if l is None:
        with open(path, "rb") as f:
            return sum(1 for _ in f)
    got = l.srml_csv_count_rows(path.encode())
    if got < 0:
        raise RuntimeError(f"srml_csv_count_rows failed: {got}")
    return int(got)


def load_csv(path: str, rows: Optional[int] = None, cols: int = 0, skip_rows: int = 0, delimiter: str = ",") -> np.ndarray:
    """Threaded numeric-CSV load into an f32 matrix (falls back to
    np.loadtxt).  rows=None sizes the destination with a native row count."""
    if rows is None:
        rows = csv_count_rows(path) - skip_rows
    l = lib()
    if l is None:
        out = np.loadtxt(path, delimiter=delimiter, skiprows=skip_rows, dtype=np.float32, ndmin=2)
        return out[:rows, :cols]
    dst = np.empty((rows, cols), dtype=np.float32, order="C")
    got = l.srml_load_csv_f32(
        path.encode(), rows, cols, skip_rows, delimiter.encode(), dst.ctypes.data_as(_c_float_p)
    )
    if got < 0:
        raise RuntimeError(f"srml_load_csv_f32 failed: {got}")
    return dst[:got]


def covariance(X: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """(cov, mean) of row-major X, threaded (fallback: numpy). Sample
    covariance with n-1 denominator, matching the reference JNI cov path
    (RapidsRowMatrix.scala:110-141)."""
    X = np.ascontiguousarray(X, dtype=np.float64)
    n, d = X.shape
    l = lib()
    if l is None or n < 2:
        mean = X.mean(axis=0)
        return np.cov(X, rowvar=False, bias=False).reshape(d, d), mean
    xtx = np.zeros((d, d), dtype=np.float64)
    colsum = np.zeros(d, dtype=np.float64)
    rc = l.srml_cov_accumulate(
        X.ctypes.data_as(_c_double_p), n, d,
        xtx.ctypes.data_as(_c_double_p), colsum.ctypes.data_as(_c_double_p),
    )
    if rc != 0:
        raise RuntimeError(f"srml_cov_accumulate failed: {rc}")
    mean = np.zeros(d, dtype=np.float64)
    rc = l.srml_cov_finalize(
        xtx.ctypes.data_as(_c_double_p), colsum.ctypes.data_as(_c_double_p),
        n, d, mean.ctypes.data_as(_c_double_p),
    )
    if rc != 0:
        raise RuntimeError(f"srml_cov_finalize failed: {rc}")
    return xtx, mean


def eigh_descending(A: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """(eigenvalues desc, components rows) with deterministic signs — the
    calSVD semantics (rapidsml_jni.cu:215-269).

    Routing: the cyclic-Jacobi C++ kernel is cache-friendly and fastest for
    small matrices; past ~256 columns LAPACK's blocked dsyevd (multithreaded
    BLAS) wins, so large problems go through numpy with the same descending
    order + sign convention applied."""
    A = np.ascontiguousarray(A, dtype=np.float64)
    d = A.shape[0]
    l = lib()
    if l is None or d > 256:
        w, v = np.linalg.eigh(A)
        w, v = w[::-1].copy(), v[:, ::-1].T.copy()
        for i in range(d):
            m = np.argmax(np.abs(v[i]))
            if v[i, m] < 0:
                v[i] = -v[i]
        return w, v
    work = A.copy()
    evals = np.zeros(d, dtype=np.float64)
    evecs = np.zeros((d, d), dtype=np.float64)
    rc = l.srml_eigh_jacobi(
        work.ctypes.data_as(_c_double_p), d,
        evals.ctypes.data_as(_c_double_p), evecs.ctypes.data_as(_c_double_p),
    )
    if rc != 0:
        raise RuntimeError(f"srml_eigh_jacobi failed: {rc}")
    return evals, evecs


def topk_select(dists: np.ndarray, k: int, id_base: int = 0) -> Tuple[np.ndarray, np.ndarray]:
    """Per-row k smallest of an (n, m) f32 tile -> (dists (n,k), ids (n,k))."""
    dists = np.ascontiguousarray(dists, dtype=np.float32)
    n, m = dists.shape
    k = min(k, m)
    l = lib()
    if l is None:
        idx = np.argpartition(dists, k - 1, axis=1)[:, :k]
        part = np.take_along_axis(dists, idx, axis=1)
        order = np.argsort(part, axis=1, kind="stable")
        return np.take_along_axis(part, order, axis=1), np.take_along_axis(idx, order, axis=1) + id_base
    out_d = np.empty((n, k), dtype=np.float32)
    out_i = np.empty((n, k), dtype=np.int64)
    rc = l.srml_topk_select(
        dists.ctypes.data_as(_c_float_p), n, m, k, id_base,
        out_d.ctypes.data_as(_c_float_p), out_i.ctypes.data_as(_c_int64_p),
    )
    if rc != 0:
        raise RuntimeError(f"srml_topk_select failed: {rc}")
    return out_d, out_i


def topk_merge(
    da: np.ndarray, ia: np.ndarray, db: np.ndarray, ib: np.ndarray
) -> Tuple[np.ndarray, np.ndarray]:
    """Merge two per-row sorted candidate lists (n,k) -> best k (in-place on
    copies of the first pair)."""
    da = np.ascontiguousarray(da, dtype=np.float32).copy()
    ia = np.ascontiguousarray(ia, dtype=np.int64).copy()
    db = np.ascontiguousarray(db, dtype=np.float32)
    ib = np.ascontiguousarray(ib, dtype=np.int64)
    n, k = da.shape
    l = lib()
    if l is None:
        alld = np.concatenate([da, db], axis=1)
        alli = np.concatenate([ia, ib], axis=1)
        order = np.argsort(alld, axis=1, kind="stable")[:, :k]
        return np.take_along_axis(alld, order, axis=1), np.take_along_axis(alli, order, axis=1)
    rc = l.srml_topk_merge(
        da.ctypes.data_as(_c_float_p), ia.ctypes.data_as(_c_int64_p),
        db.ctypes.data_as(_c_float_p), ib.ctypes.data_as(_c_int64_p), n, k,
    )
    if rc != 0:
        raise RuntimeError(f"srml_topk_merge failed: {rc}")
    return da, ia
