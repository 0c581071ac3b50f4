"""ctypes bindings for the native C++ columnar codecs (native/codecs.cpp).

The native library accelerates the host-side transcoding between the
variable-length column formats and dense numpy arrays (the input/output of
the TPU engine). The library is not committed: the first load builds it
from codecs.cpp with ``make -C native`` (and rebuilds it when the source is
newer). Falls back to the pure-Python codecs when it cannot be built;
`available()` reports which path is active.
"""
# amlint: host-only — pure-host layer: must not import tpu/ or jax
from __future__ import annotations

import ctypes
import os
import subprocess

import numpy as np

NULL_SENTINEL = -(2**62)

_NATIVE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "native"
)
_LIB_PATH = os.path.join(_NATIVE_DIR, "libamcodecs.so")
_SRC_PATH = os.path.join(_NATIVE_DIR, "codecs.cpp")
_lib = None
_build_tried = False


def _stale() -> bool:
    """True when the library is missing or older than its source."""
    if not os.path.exists(_LIB_PATH):
        return True
    return (os.path.exists(_SRC_PATH)
            and os.path.getmtime(_LIB_PATH) < os.path.getmtime(_SRC_PATH))


def _build() -> None:
    """Builds the library into a temp name and renames it into place, so
    processes building at once (the xdist workers) never load a
    half-written file. A failed build leaves the pure-Python codecs on."""
    tmp = f"{_LIB_PATH}.{os.getpid()}.tmp"
    try:
        done = subprocess.run(["make", "-C", _NATIVE_DIR, f"LIB={tmp}"],
                              capture_output=True, text=True)
    except OSError:  # no make on this host
        return
    if done.returncode == 0:
        os.replace(tmp, _LIB_PATH)
    elif os.path.exists(tmp):
        os.unlink(tmp)


def _load():
    global _lib, _build_tried
    if _lib is not None:
        return _lib
    if not _build_tried and _stale():
        _build_tried = True
        _build()
    if not os.path.exists(_LIB_PATH):
        return None
    lib = ctypes.CDLL(_LIB_PATH)
    u8p = ctypes.POINTER(ctypes.c_uint8)
    i64p = ctypes.POINTER(ctypes.c_int64)
    lib.am_rle_decode.restype = ctypes.c_int64
    lib.am_rle_decode.argtypes = [u8p, ctypes.c_size_t, ctypes.c_int,
                                  ctypes.c_int64, i64p, ctypes.c_size_t]
    lib.am_rle_encode.restype = ctypes.c_int64
    lib.am_rle_encode.argtypes = [i64p, ctypes.c_size_t, ctypes.c_int,
                                  ctypes.c_int64, u8p, ctypes.c_size_t]
    lib.am_delta_decode.restype = ctypes.c_int64
    lib.am_delta_decode.argtypes = [u8p, ctypes.c_size_t, ctypes.c_int64,
                                    i64p, ctypes.c_size_t]
    lib.am_delta_encode.restype = ctypes.c_int64
    lib.am_delta_encode.argtypes = [i64p, ctypes.c_size_t, ctypes.c_int64,
                                    u8p, ctypes.c_size_t]
    lib.am_bool_decode.restype = ctypes.c_int64
    lib.am_bool_decode.argtypes = [u8p, ctypes.c_size_t, u8p, ctypes.c_size_t]
    lib.am_bool_encode.restype = ctypes.c_int64
    lib.am_bool_encode.argtypes = [u8p, ctypes.c_size_t, u8p, ctypes.c_size_t]
    if hasattr(lib, "am_strrle_decode"):
        lib.am_strrle_decode.restype = ctypes.c_int64
        lib.am_strrle_decode.argtypes = [u8p, ctypes.c_size_t, u8p,
                                         ctypes.c_size_t, i64p, ctypes.c_size_t]
    _lib = lib
    return lib


def available() -> bool:
    return _load() is not None


def _check(rc, what):
    if rc < 0:
        raise ValueError(f"native {what} failed with code {rc}")
    return rc


def _as_u8p(buf):
    return ctypes.cast(ctypes.c_char_p(bytes(buf)), ctypes.POINTER(ctypes.c_uint8))


def rle_decode(buf: bytes, signed: bool = False, max_count: int = None) -> np.ndarray:
    """Decodes an RLE column into an int64 array (nulls = NULL_SENTINEL)."""
    lib = _load()
    cap = max_count if max_count is not None else max(16, len(buf) * 64)
    out = np.empty(cap, np.int64)
    rc = lib.am_rle_decode(
        _as_u8p(buf), len(buf), 1 if signed else 0, NULL_SENTINEL,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)), cap,
    )
    return out[:_check(rc, "rle_decode")]


def rle_encode(values: np.ndarray, signed: bool = False) -> bytes:
    lib = _load()
    values = np.ascontiguousarray(values, np.int64)
    cap = max(16, values.size * 10)
    out = np.empty(cap, np.uint8)
    rc = lib.am_rle_encode(
        values.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)), values.size,
        1 if signed else 0, NULL_SENTINEL,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)), cap,
    )
    return out[:_check(rc, "rle_encode")].tobytes()


def delta_decode(buf: bytes, max_count: int = None) -> np.ndarray:
    lib = _load()
    cap = max_count if max_count is not None else max(16, len(buf) * 64)
    out = np.empty(cap, np.int64)
    rc = lib.am_delta_decode(
        _as_u8p(buf), len(buf), NULL_SENTINEL,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)), cap,
    )
    return out[:_check(rc, "delta_decode")]


def delta_encode(values: np.ndarray) -> bytes:
    lib = _load()
    values = np.ascontiguousarray(values, np.int64)
    cap = max(16, values.size * 10)
    out = np.empty(cap, np.uint8)
    rc = lib.am_delta_encode(
        values.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)), values.size,
        NULL_SENTINEL,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)), cap,
    )
    return out[:_check(rc, "delta_encode")].tobytes()


def bool_decode(buf: bytes, max_count: int = None) -> np.ndarray:
    lib = _load()
    cap = max_count if max_count is not None else max(16, len(buf) * 4096)
    out = np.empty(cap, np.uint8)
    rc = lib.am_bool_decode(
        _as_u8p(buf), len(buf),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)), cap,
    )
    return out[:_check(rc, "bool_decode")].astype(bool)


def strrle_decode(buf: bytes, max_count: int = None):
    """Decodes a string-RLE column; returns (blob bytes, offsets int64[n,2])
    where a row's string is blob[start:end], or (-1, -1) for null."""
    lib = _load()
    if not hasattr(lib, "am_strrle_decode"):
        raise AttributeError("native library predates am_strrle_decode; rebuild")
    cap = max_count if max_count is not None else max(16, len(buf) * 64)
    blob_cap = max(64, len(buf) * 64)
    blob = np.empty(blob_cap, np.uint8)
    offs = np.empty(cap * 2, np.int64)
    rc = lib.am_strrle_decode(
        _as_u8p(buf), len(buf),
        blob.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)), blob_cap,
        offs.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)), cap,
    )
    n = _check(rc, "strrle_decode")
    return blob.tobytes(), offs[: 2 * n].reshape(n, 2)


def bool_encode(values: np.ndarray) -> bytes:
    lib = _load()
    values = np.ascontiguousarray(values, np.uint8)
    cap = max(16, values.size * 10 + 16)
    out = np.empty(cap, np.uint8)
    rc = lib.am_bool_encode(
        values.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)), values.size,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)), cap,
    )
    return out[:_check(rc, "bool_encode")].tobytes()


if __name__ == "__main__":
    print("native codecs active" if available() else
          "native codecs unavailable (make -C native failed)")
