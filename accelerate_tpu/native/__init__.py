"""ctypes bindings for the native host-runtime kernels (host_runtime.cpp).

The shared library is never committed: it is built from ``host_runtime.cpp``
with the system g++ on first use and cached next to the source, so a fresh
checkout and a long-lived working tree behave the same. Everything runs on
numpy when the build fails (logged once, with the compiler's message) or
``ACCELERATE_DISABLE_NATIVE=1`` is set, so the package never hard-requires a
toolchain. ``get_lib() is not None`` says which of the two is in use.
"""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import threading

import numpy as np

logger = logging.getLogger(__name__)

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "host_runtime.cpp")
_LIB_PATH = os.path.join(_HERE, "libhost_runtime.so")

_lock = threading.Lock()
_lib = None
_lib_failed = False

# Below this many bytes a plain numpy fancy-index wins; and on a single-core
# host the parallel path cannot beat numpy's memcpy loop at all, so the
# native kernels only engage with >=2 cores (real TPU-VM hosts have ~100).
NATIVE_MIN_BYTES = 1 << 20
_NUM_THREADS = min(8, os.cpu_count() or 1)
_MULTICORE = (os.cpu_count() or 1) >= 2


def native_disabled() -> bool:
    return os.environ.get("ACCELERATE_DISABLE_NATIVE", "").lower() in ("1", "true", "yes")


def _build() -> bool:
    # Compile to a per-process temp name, then atomically rename: several
    # launched ranks on one host may build concurrently, and dlopen of a
    # partially-linked file must be impossible.
    tmp = f"{_LIB_PATH}.{os.getpid()}.tmp"
    cmd = [
        "g++", "-O3", "-shared", "-fPIC", "-pthread", "-std=c++17",
        _SRC, "-o", tmp,
    ]
    try:
        result = subprocess.run(cmd, capture_output=True, text=True, timeout=120)
        if result.returncode != 0:
            logger.warning("native host runtime: g++ failed, using numpy: %s",
                           result.stderr[-500:])
            return False
        os.replace(tmp, _LIB_PATH)
        return True
    except (OSError, subprocess.TimeoutExpired) as e:
        logger.warning("native host runtime: build did not run, using numpy: %s", e)
        return False
    finally:
        if os.path.exists(tmp):
            try:
                os.remove(tmp)
            except OSError:
                pass


def get_lib():
    """The loaded library, building it if needed; None when unavailable."""
    global _lib, _lib_failed
    if _lib is not None or _lib_failed or native_disabled():
        return _lib
    with _lock:
        if _lib is not None or _lib_failed:
            return _lib
        try:
            stale = (
                not os.path.exists(_LIB_PATH)
                or os.path.getmtime(_LIB_PATH) < os.path.getmtime(_SRC)
            )
            if stale and not _build():
                _lib_failed = True
                return None
            lib = ctypes.CDLL(_LIB_PATH)
            lib.at_gather_rows.argtypes = [
                ctypes.c_void_p, ctypes.c_int64, ctypes.c_void_p,
                ctypes.c_int64, ctypes.c_void_p, ctypes.c_int,
            ]
            lib.at_stack_ptrs.argtypes = [
                ctypes.POINTER(ctypes.c_void_p), ctypes.c_int64,
                ctypes.c_int64, ctypes.c_void_p, ctypes.c_int,
            ]
            lib.at_gather_columns.argtypes = [
                ctypes.POINTER(ctypes.c_void_p), ctypes.c_void_p,
                ctypes.c_int64, ctypes.c_void_p, ctypes.c_int64,
                ctypes.POINTER(ctypes.c_void_p), ctypes.c_int,
            ]
            lib.at_pread_segments.argtypes = [
                ctypes.c_char_p, ctypes.c_void_p, ctypes.c_void_p,
                ctypes.POINTER(ctypes.c_void_p), ctypes.c_int64, ctypes.c_int,
            ]
            lib.at_pread_segments.restype = ctypes.c_int
            lib.at_pwrite_segments.argtypes = [
                ctypes.c_char_p, ctypes.c_char_p, ctypes.c_int64,
                ctypes.c_void_p, ctypes.c_void_p,
                ctypes.POINTER(ctypes.c_void_p), ctypes.c_int64, ctypes.c_int,
            ]
            lib.at_pwrite_segments.restype = ctypes.c_int
            lib.at_version.restype = ctypes.c_int
            assert lib.at_version() == 3
            _lib = lib
        except Exception as e:  # host-side optimisation only: numpy takes over
            logger.warning("native host runtime: load failed, using numpy: %s", e)
            _lib_failed = True
    return _lib


def _normalize_indices(indices, n: int):
    """int64 contiguous in-range indices for the native path, or None when
    numpy's richer semantics (bool masks, negatives out of a simple wrap,
    IndexError on out-of-range) must handle it."""
    arr = np.asarray(indices)
    if arr.dtype == bool:
        return None
    idx = np.ascontiguousarray(arr, dtype=np.int64)
    if idx.size and (idx.min() < -n or idx.max() >= n):
        return None  # let numpy raise the IndexError
    if idx.size and idx.min() < 0:
        idx = np.where(idx < 0, idx + n, idx)
        idx = np.ascontiguousarray(idx)
    return idx


def gather_rows(src: np.ndarray, indices, force: bool = False) -> np.ndarray:
    """out[j] = src[indices[j]] — parallel memcpy gather for large batches,
    numpy fancy indexing otherwise."""
    idx = _normalize_indices(indices, len(src))
    if idx is None:  # bool mask / negative / out-of-range → numpy semantics
        return src[np.asarray(indices)]
    row_bytes = src.dtype.itemsize * int(np.prod(src.shape[1:], dtype=np.int64))
    total = row_bytes * len(idx)
    eligible = force or (_MULTICORE and total >= NATIVE_MIN_BYTES)
    lib = get_lib() if eligible else None
    if lib is None or not src.flags.c_contiguous or src.dtype.hasobject:
        return src[idx]
    out = np.empty((len(idx),) + src.shape[1:], dtype=src.dtype)
    lib.at_gather_rows(
        src.ctypes.data, row_bytes, idx.ctypes.data, len(idx),
        out.ctypes.data, _NUM_THREADS,
    )
    return out


def gather_columns(columns: dict[str, np.ndarray], indices, force: bool = False) -> dict[str, np.ndarray]:
    """One-call batch assembly for a dict-of-arrays dataset."""
    names = list(columns)
    arrays = [columns[k] for k in names]
    idx = _normalize_indices(indices, len(arrays[0]))
    if idx is None:
        return {k: columns[k][np.asarray(indices)] for k in names}
    total = sum(
        a.dtype.itemsize * int(np.prod(a.shape[1:], dtype=np.int64)) for a in arrays
    ) * len(idx)
    eligible = force or (_MULTICORE and total >= NATIVE_MIN_BYTES)
    lib = get_lib() if eligible else None
    if lib is None or not all(
        a.flags.c_contiguous and not a.dtype.hasobject for a in arrays
    ):
        return {k: columns[k][idx] for k in names}
    outs = [np.empty((len(idx),) + a.shape[1:], dtype=a.dtype) for a in arrays]
    n = len(arrays)
    srcs = (ctypes.c_void_p * n)(*[a.ctypes.data for a in arrays])
    dsts = (ctypes.c_void_p * n)(*[o.ctypes.data for o in outs])
    row_bytes = np.asarray(
        [a.dtype.itemsize * int(np.prod(a.shape[1:], dtype=np.int64)) for a in arrays],
        dtype=np.int64,
    )
    lib.at_gather_columns(
        srcs, row_bytes.ctypes.data, n, idx.ctypes.data, len(idx), dsts, _NUM_THREADS
    )
    return dict(zip(names, outs))


_ST_DTYPES = {
    "F64": np.float64, "F32": np.float32, "F16": np.float16,
    "I64": np.int64, "I32": np.int32, "I16": np.int16, "I8": np.int8,
    "U8": np.uint8, "BOOL": np.bool_,
}


def load_safetensors_fast(path: str, force: bool = False):
    """Whole-file safetensors load with parallel positioned reads.

    Parses the header in Python (8-byte LE length + JSON) and hands every
    tensor's byte range to ``at_pread_segments`` — hundreds of page-cache
    memcpys spread over the pool instead of the safetensors lib's serial
    per-tensor copies. Returns None when the native path can't serve the file
    (no lib, unknown dtype) so callers fall back to the safetensors lib.
    """
    import json

    lib = get_lib()
    if lib is None:
        return None
    try:
        with open(path, "rb") as f:
            hlen = int.from_bytes(f.read(8), "little")
            header = json.loads(f.read(hlen))
    except (OSError, ValueError):
        return None
    base = 8 + hlen
    names, offs, sizes, outs = [], [], [], []
    for name, meta in header.items():
        if name == "__metadata__":
            continue
        st_dtype = meta["dtype"]
        if st_dtype == "BF16":
            import ml_dtypes

            dtype = np.dtype(ml_dtypes.bfloat16)
        elif st_dtype in _ST_DTYPES:
            dtype = np.dtype(_ST_DTYPES[st_dtype])
        else:
            return None
        b0, b1 = meta["data_offsets"]
        arr = np.empty(meta["shape"], dtype=dtype)
        if arr.nbytes != b1 - b0:
            return None
        names.append(name)
        offs.append(base + b0)
        sizes.append(b1 - b0)
        outs.append(arr)
    if not names:
        return {}
    total = sum(sizes)
    if not force and not (_MULTICORE and total >= NATIVE_MIN_BYTES):
        return None  # small files: the safetensors lib's mmap is fine
    n = len(names)
    dsts = (ctypes.c_void_p * n)(*[a.ctypes.data for a in outs])
    offs_a = np.ascontiguousarray(offs, dtype=np.int64)
    sizes_a = np.ascontiguousarray(sizes, dtype=np.int64)
    rc = lib.at_pread_segments(
        os.fsencode(path), offs_a.ctypes.data, sizes_a.ctypes.data, dsts, n,
        _NUM_THREADS,
    )
    if rc != 0:
        return None
    return dict(zip(names, outs))


def _st_dtype_name(dtype: np.dtype):
    """numpy dtype → safetensors dtype string, or None when unsupported."""
    try:
        import ml_dtypes

        if dtype == np.dtype(ml_dtypes.bfloat16):
            return "BF16"
    except ImportError:
        pass
    for name, np_dtype in _ST_DTYPES.items():
        if dtype == np.dtype(np_dtype):
            return name
    return None


def save_safetensors_fast(state_dict, path: str, force: bool = False) -> bool:
    """Whole-file safetensors save with parallel positioned writes — the
    twin of :func:`load_safetensors_fast` (native/host_runtime.cpp
    ``at_pwrite_segments``). Builds the spec header in Python (8-byte LE
    length + JSON, space-padded so data starts 8-aligned) and fans the
    tensor payloads over the pool with one fsync at the end. Returns False
    when the native path can't serve the dict (no lib, unknown dtype, small
    file) so callers fall back to the safetensors lib."""
    import json

    lib = get_lib()
    if lib is None:
        return False
    arrays, header, cur = {}, {}, 0
    for name, arr in state_dict.items():
        arr = np.ascontiguousarray(np.asarray(arr))
        st_name = _st_dtype_name(arr.dtype)
        if st_name is None or arr.dtype.hasobject:
            return False
        arrays[name] = arr
        header[name] = {
            "dtype": st_name,
            "shape": list(arr.shape),
            "data_offsets": [cur, cur + arr.nbytes],
        }
        cur += arr.nbytes
    if not force and not (_MULTICORE and cur >= NATIVE_MIN_BYTES):
        return False
    hjson = json.dumps(header, separators=(",", ":")).encode()
    pad = -(8 + len(hjson)) % 8  # spec: pad with spaces, data 8-aligned
    hjson += b" " * pad
    blob = len(hjson).to_bytes(8, "little") + hjson
    base = len(blob)
    n = len(arrays)
    if n == 0:
        with open(path, "wb") as f:
            f.write(blob)
        return True
    outs = list(arrays.values())
    srcs = (ctypes.c_void_p * n)(*[a.ctypes.data for a in outs])
    offs = np.ascontiguousarray(
        [base + header[k]["data_offsets"][0] for k in arrays], dtype=np.int64
    )
    sizes = np.ascontiguousarray([a.nbytes for a in outs], dtype=np.int64)
    rc = lib.at_pwrite_segments(
        os.fsencode(path), blob, len(blob), offs.ctypes.data, sizes.ctypes.data,
        srcs, n, _NUM_THREADS,
    )
    return rc == 0


def stack_items(items: list, force: bool = False) -> np.ndarray:
    """np.stack with a parallel-memcpy fast path for big uniform items."""
    first = np.asarray(items[0])
    item_bytes = first.nbytes
    total = item_bytes * len(items)
    eligible = force or (_MULTICORE and total >= NATIVE_MIN_BYTES)
    lib = get_lib() if eligible else None
    arrays = [np.asarray(x) for x in items]
    if (
        lib is None
        or first.dtype.hasobject
        or not all(
            a.flags.c_contiguous and a.shape == first.shape and a.dtype == first.dtype
            for a in arrays
        )
    ):
        return np.stack(arrays)
    out = np.empty((len(arrays),) + first.shape, dtype=first.dtype)
    ptrs = (ctypes.c_void_p * len(arrays))(*[a.ctypes.data for a in arrays])
    lib.at_stack_ptrs(ptrs, item_bytes, len(arrays), out.ctypes.data, _NUM_THREADS)
    return out
