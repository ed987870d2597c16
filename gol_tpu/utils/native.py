"""ctypes bindings to the native C++ runtime helpers (``native/golrt``).

The reference's host runtime is native C/CUDA; our TPU compute path is
XLA-compiled, but the host-side runtime hot spots — formatting multi-GB
world dumps (gol_printWorld, gol-main.c:17-28) and bit-pack/unpack between
the dense and bit-packed engines — are implemented in C++
(``native/golrt.cpp``) and loaded here via ctypes.  Every entry point has a
pure-Python fallback (in :mod:`gol_tpu.utils.io` / :mod:`gol_tpu.ops.bitlife`);
``available()`` gates usage so the framework works before ``make -C native``.
"""

from __future__ import annotations

import ctypes
import os
from typing import Optional

import numpy as np

_LIB_NAMES = ("libgolrt.so",)
_lib: Optional[ctypes.CDLL] = None
_load_attempted = False


_REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
_SOURCE = os.path.join(_REPO, "native", "golrt.cpp")


def _candidate_paths():
    for name in _LIB_NAMES:
        yield os.path.join(_REPO, "native", name)
        yield os.path.join(_REPO, name)


def _stale(path: str) -> bool:
    """A library older than its committed source was built from other
    code, or on another machine: never load it (``make -C native``
    rebuilds it where it runs)."""
    return os.path.exists(_SOURCE) and (
        os.path.getmtime(path) < os.path.getmtime(_SOURCE)
    )


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _load_attempted
    if _load_attempted:
        return _lib
    _load_attempted = True
    for path in _candidate_paths():
        if os.path.exists(path) and not _stale(path):
            try:
                lib = ctypes.CDLL(path)
            except OSError:
                continue
            lib.golrt_format_world_size.restype = ctypes.c_size_t
            lib.golrt_format_world_size.argtypes = [
                ctypes.c_int64,
                ctypes.c_int64,
                ctypes.c_int64,
            ]
            lib.golrt_format_world.restype = ctypes.c_size_t
            lib.golrt_format_world.argtypes = [
                ctypes.POINTER(ctypes.c_uint8),
                ctypes.c_int64,
                ctypes.c_int64,
                ctypes.c_int64,
                ctypes.POINTER(ctypes.c_char),
            ]
            lib.golrt_write_rank_file.restype = ctypes.c_int
            lib.golrt_write_rank_file.argtypes = [
                ctypes.c_char_p,
                ctypes.POINTER(ctypes.c_uint8),
                ctypes.c_int64,
                ctypes.c_int64,
                ctypes.c_int64,
            ]
            lib.golrt_pack_bits.restype = None
            lib.golrt_pack_bits.argtypes = [
                ctypes.POINTER(ctypes.c_uint8),
                ctypes.c_int64,
                ctypes.POINTER(ctypes.c_uint32),
            ]
            lib.golrt_unpack_bits.restype = None
            lib.golrt_unpack_bits.argtypes = [
                ctypes.POINTER(ctypes.c_uint32),
                ctypes.c_int64,
                ctypes.POINTER(ctypes.c_uint8),
            ]
            _lib = lib
            break
    return _lib


def available() -> bool:
    return _load() is not None


def reload() -> bool:
    """Forget the memoized load and probe again: for a process that built
    the library after its first probe (which memoized the miss)."""
    global _lib, _load_attempted
    _lib, _load_attempted = None, False
    return available()


def format_world(block: np.ndarray, rank: int) -> bytes:
    """Native renderer; byte-identical to utils.io.format_world."""
    lib = _load()
    assert lib is not None
    block = np.ascontiguousarray(block, dtype=np.uint8)
    h, w = block.shape
    size = lib.golrt_format_world_size(h, w, h * rank)
    buf = ctypes.create_string_buffer(size)
    n = lib.golrt_format_world(
        block.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)), h, w, h * rank, buf
    )
    return buf.raw[:n]


def write_rank_file(path: str, block: np.ndarray, rank: int) -> None:
    lib = _load()
    assert lib is not None
    block = np.ascontiguousarray(block, dtype=np.uint8)
    h, w = block.shape
    rc = lib.golrt_write_rank_file(
        path.encode(), block.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)), h, w, rank
    )
    if rc != 0:
        raise OSError(f"native writer failed for {path} (rc={rc})")


def pack_bits(cells: np.ndarray) -> np.ndarray:
    """uint8[n*32] 0/1 cells -> uint32[n] words, bit i of word j = cell j*32+i."""
    lib = _load()
    assert lib is not None
    cells = np.ascontiguousarray(cells, dtype=np.uint8)
    assert cells.size % 32 == 0
    out = np.empty(cells.size // 32, dtype=np.uint32)
    lib.golrt_pack_bits(
        cells.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        cells.size,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)),
    )
    return out


def unpack_bits(words: np.ndarray) -> np.ndarray:
    lib = _load()
    assert lib is not None
    words = np.ascontiguousarray(words, dtype=np.uint32)
    out = np.empty(words.size * 32, dtype=np.uint8)
    lib.golrt_unpack_bits(
        words.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)),
        words.size,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
    )
    return out
