"""ctypes binding for the native (C++) token loader.

The mechanism half of the data pipeline in native code (``native/
token_loader.cc``): mmap'ed token file, int-width conversion, and a worker
thread that gathers the *next* batch while the current step runs — the role
the reference delegates to torch DataLoader's C++ workers
(training_utils.py:99). Policy (epoch shuffle, dp sharding, resume) stays in
:mod:`.dataset`; this module only accelerates sample gathering.

The shared library builds on demand with ``g++`` (no pybind11 — plain C ABI
via ctypes, per the environment constraints) next to the source, and
rebuilds whenever it is missing or older than ``token_loader.cc`` — it is a
git-ignored build product, so a copy that rode along from another checkout
is never trusted over the source. :func:`native_available` is False when no
toolchain exists; a caller that was *asked* for the native loader treats
that as an error (``examples/pretrain_llama.py --native-loader``).
"""

from __future__ import annotations

import ctypes
import os
import subprocess
from typing import Optional

import numpy as np

from neuronx_distributed_llama3_2_tpu.utils.logger import get_logger

logger = get_logger()

_NATIVE_DIR = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "..", "native"
)
_SO_PATH = os.path.join(_NATIVE_DIR, "libtoken_loader.so")
_SRC_PATH = os.path.join(_NATIVE_DIR, "token_loader.cc")
_LIB: Optional[ctypes.CDLL] = None
_BUILD_FAILED = False


def _load_lib() -> Optional[ctypes.CDLL]:
    global _LIB, _BUILD_FAILED
    if _LIB is not None:
        return _LIB
    if _BUILD_FAILED:
        return None
    if not os.path.exists(_SO_PATH) or (
        os.path.getmtime(_SO_PATH) < os.path.getmtime(_SRC_PATH)
    ):
        try:
            subprocess.run(
                ["make", "-C", _NATIVE_DIR],
                check=True,
                capture_output=True,
                timeout=120,
            )
        except (OSError, subprocess.SubprocessError) as e:
            logger.info("native token loader unavailable (%s)", e)
            _BUILD_FAILED = True
            return None
    lib = ctypes.CDLL(_SO_PATH)
    lib.tl_open.restype = ctypes.c_void_p
    lib.tl_open.argtypes = [
        ctypes.c_char_p, ctypes.c_longlong, ctypes.c_longlong, ctypes.c_int,
        ctypes.c_int,
    ]
    lib.tl_close.argtypes = [ctypes.c_void_p]
    lib.tl_num_tokens.restype = ctypes.c_longlong
    lib.tl_num_tokens.argtypes = [ctypes.c_void_p]
    lib.tl_gather.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_longlong), ctypes.c_int,
        ctypes.c_int, ctypes.POINTER(ctypes.c_int32),
    ]
    lib.tl_prefetch.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_longlong), ctypes.c_int,
        ctypes.c_int,
    ]
    lib.tl_wait.restype = ctypes.c_longlong
    lib.tl_wait.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_int32), ctypes.c_longlong,
    ]
    _LIB = lib
    return lib


def native_available() -> bool:
    return _load_lib() is not None


def _npy_layout(path: str):
    """(data_offset, n_tokens, token_bytes, is_signed) of a 1-D
    little-endian int .npy."""
    arr = np.load(path, mmap_mode="r")
    if arr.ndim != 1:
        raise ValueError(f"token file must be 1-D, got {arr.shape}")
    if arr.dtype.byteorder == ">":
        raise ValueError("big-endian token files are not supported natively")
    if arr.dtype.kind not in ("i", "u") or arr.dtype.itemsize not in (1, 2, 4, 8):
        raise ValueError(f"unsupported token dtype {arr.dtype}")
    offset = arr.offset if hasattr(arr, "offset") else None
    if offset is None:  # pragma: no cover - old numpy
        with open(path, "rb") as f:
            np.lib.format.read_magic(f)
            np.lib.format.read_array_header_1_0(f)
            offset = f.tell()
    return (
        int(offset),
        int(arr.shape[0]),
        int(arr.dtype.itemsize),
        arr.dtype.kind == "i",
    )


class NativeTokenDataset:
    """Drop-in for :class:`.dataset.TokenDataset` backed by the C++ loader,
    with batch-gather and prefetch entry points the loader uses."""

    def __init__(self, path: str, seq_len: int):
        lib = _load_lib()
        if lib is None:
            raise RuntimeError("native token loader not available")
        self._lib = lib
        off, n, width, signed = _npy_layout(path)
        self._h = lib.tl_open(path.encode(), off, n, width, int(signed))
        if not self._h:
            raise RuntimeError(f"tl_open failed for {path}")
        self.seq_len = seq_len
        self._n_tokens = n

    def __len__(self) -> int:
        return self._n_tokens // self.seq_len

    def __getitem__(self, i: int) -> np.ndarray:
        return self.gather(np.asarray([i], np.int64))[0]

    def gather(self, indices: np.ndarray) -> np.ndarray:
        """(count, seq_len) int32 batch for explicit sample indices."""
        idx = np.ascontiguousarray(indices, np.int64)
        out = np.empty((len(idx), self.seq_len), np.int32)
        self._lib.tl_gather(
            self._h,
            idx.ctypes.data_as(ctypes.POINTER(ctypes.c_longlong)),
            len(idx),
            self.seq_len,
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        )
        return out

    def prefetch(self, indices: np.ndarray) -> None:
        """Post the next batch's indices to the background worker."""
        idx = np.ascontiguousarray(indices, np.int64)
        self._pending_shape = (len(idx), self.seq_len)
        self._lib.tl_prefetch(
            self._h,
            idx.ctypes.data_as(ctypes.POINTER(ctypes.c_longlong)),
            len(idx),
            self.seq_len,
        )

    def wait(self) -> np.ndarray:
        """Block for (and return) the prefetched batch."""
        count, seq = self._pending_shape
        out = np.empty((count, seq), np.int32)
        n = self._lib.tl_wait(
            self._h,
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            out.size,
        )
        if n != out.size:
            raise RuntimeError(f"tl_wait returned {n}, expected {out.size}")
        return out

    def close(self) -> None:
        if getattr(self, "_h", None):
            self._lib.tl_close(self._h)
            self._h = None

    def __del__(self):  # pragma: no cover - GC timing
        try:
            self.close()
        except Exception:
            pass
