"""Native (C++) host-runtime components, loaded via ctypes.

The serving engine's host-side hot path — page allocation, prefix-cache
probing, block hashing — runs here when the compiled library is available,
with the pure-Python implementations in :mod:`runbookai_tpu.engine.kv_cache`
as a behavior-identical fallback (the test suite diffs the two backends over
randomized op sequences).

Build model: a single translation unit (``src/runtime.cpp``) compiled on
first use with ``g++ -O2 -shared -fPIC`` into the git-ignored ``_build/``.
The library's file name carries the SHA-256 of the source and the compile
line, so the only library ever loaded is one built from the source in this
checkout: an older build, or a ``.so`` somebody copied in, has another name
and is never opened. No pybind11 (not in the image) — plain C ABI + ctypes.
``RUNBOOKAI_NATIVE=0`` selects the Python allocator; so does a build that
fails, with the compiler's message logged as a warning. Either way
:func:`backend` says which allocator serves (``/healthz`` ``runtime``
block, ``chip_smoke.py``).

The reference has no first-party native code (SURVEY.md §2.9); this module is
new construction for the TPU build's runtime layer.
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
from pathlib import Path
from typing import Optional, Sequence

import numpy as np

_SRC = Path(__file__).parent / "src" / "runtime.cpp"
_BUILD_DIR = Path(__file__).parent / "_build"
_CXX = ("g++", "-O2", "-std=c++17", "-shared", "-fPIC")

_lib: Optional[ctypes.CDLL] = None
_load_attempted = False


def _lib_path() -> Path:
    digest = hashlib.sha256(_SRC.read_bytes() + " ".join(_CXX).encode())
    return _BUILD_DIR / f"libruntime-{digest.hexdigest()[:16]}.so"


def _compile(lib_path: Path) -> None:
    # Build to a process-private temp path and os.replace() into place so
    # concurrent first-compiles can't interleave writes into the cached .so.
    tmp = lib_path.with_suffix(f".{os.getpid()}.tmp.so")
    try:
        _BUILD_DIR.mkdir(exist_ok=True)
        res = subprocess.run([*_CXX, str(_SRC), "-o", str(tmp)],
                             capture_output=True, text=True, timeout=120)
        if res.returncode != 0:
            raise OSError(f"g++ exited {res.returncode}: "
                          f"{res.stderr.strip()[-400:]}")
        os.replace(tmp, lib_path)
    finally:
        tmp.unlink(missing_ok=True)


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _load_attempted
    if _load_attempted:
        return _lib
    _load_attempted = True
    if os.environ.get("RUNBOOKAI_NATIVE", "1") == "0":
        return None
    try:
        lib_path = _lib_path()
        if not lib_path.is_file():
            _compile(lib_path)
        lib = ctypes.CDLL(str(lib_path))
    except (OSError, subprocess.TimeoutExpired) as e:
        # No compiler, a read-only install, a source that does not build:
        # the Python allocator is behavior-identical (tests diff the two),
        # so serving goes on — but never silently.
        logging.getLogger(__name__).warning(
            "native runtime not built from %s (%s); serving with the "
            "Python page allocator", _SRC, e)
        return None

    lib.rk_alloc_create.restype = ctypes.c_void_p
    lib.rk_alloc_create.argtypes = [ctypes.c_int64]
    lib.rk_alloc_destroy.argtypes = [ctypes.c_void_p]
    lib.rk_alloc_free_pages.restype = ctypes.c_int64
    lib.rk_alloc_free_pages.argtypes = [ctypes.c_void_p]
    lib.rk_alloc_cached_pages.restype = ctypes.c_int64
    lib.rk_alloc_cached_pages.argtypes = [ctypes.c_void_p]
    lib.rk_alloc_alloc.restype = ctypes.c_int
    lib.rk_alloc_alloc.argtypes = [ctypes.c_void_p, ctypes.c_int64,
                                   ctypes.POINTER(ctypes.c_int64)]
    lib.rk_alloc_release.argtypes = [ctypes.c_void_p,
                                     ctypes.POINTER(ctypes.c_int64), ctypes.c_int64]
    lib.rk_alloc_register.argtypes = [ctypes.c_void_p, ctypes.c_int64, ctypes.c_uint64]
    lib.rk_alloc_lookup.restype = ctypes.c_int64
    lib.rk_alloc_lookup.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
    lib.rk_alloc_acquire.argtypes = [ctypes.c_void_p, ctypes.c_int64]
    lib.rk_alloc_is_retired.restype = ctypes.c_int
    lib.rk_alloc_is_retired.argtypes = [ctypes.c_void_p, ctypes.c_int64]
    lib.rk_hash_blocks.restype = ctypes.c_int64
    lib.rk_hash_blocks.argtypes = [ctypes.POINTER(ctypes.c_int32), ctypes.c_int64,
                                   ctypes.c_int64, ctypes.c_int64,
                                   ctypes.POINTER(ctypes.c_uint64)]
    _lib = lib
    return _lib


def available() -> bool:
    return _load() is not None


def backend() -> str:
    """Which page allocator / block hasher serves: ``native`` or ``python``."""
    return "native" if available() else "python"


class NativePageAllocator:
    """ctypes wrapper with the same interface as the Python ``PageAllocator``."""

    NULL_PAGE = 0

    def __init__(self, num_pages: int):
        lib = _load()
        if lib is None:
            raise RuntimeError("native runtime library unavailable")
        if num_pages < 2:
            raise ValueError("need at least 2 pages (one reserved null page)")
        self._lib = lib
        self.num_pages = num_pages
        self._h = ctypes.c_void_p(lib.rk_alloc_create(num_pages))
        if not self._h:
            raise RuntimeError("rk_alloc_create failed")

    def __del__(self):
        h = getattr(self, "_h", None)
        if h:
            self._lib.rk_alloc_destroy(h)
            self._h = None

    @property
    def free_pages(self) -> int:
        return self._lib.rk_alloc_free_pages(self._h)

    @property
    def cached_pages(self) -> int:
        return self._lib.rk_alloc_cached_pages(self._h)

    def alloc(self, n: int) -> list[int]:
        out = (ctypes.c_int64 * max(n, 1))()
        if self._lib.rk_alloc_alloc(self._h, n, out) != 0:
            raise MemoryError(
                f"KV page pool exhausted: want {n}, have {self.free_pages}")
        return list(out[:n])

    def free(self, pages: Sequence[int]) -> None:
        n = len(pages)
        arr = (ctypes.c_int64 * max(n, 1))(*pages)
        self._lib.rk_alloc_release(self._h, arr, n)

    def register(self, page: int, block_hash: int) -> None:
        self._lib.rk_alloc_register(self._h, page, block_hash & 0xFFFFFFFFFFFFFFFF)

    def lookup(self, block_hash: int) -> Optional[int]:
        p = self._lib.rk_alloc_lookup(self._h, block_hash & 0xFFFFFFFFFFFFFFFF)
        return None if p < 0 else p

    def acquire(self, page: int) -> None:
        self._lib.rk_alloc_acquire(self._h, page)

    def is_retired(self, page: int) -> bool:
        return bool(self._lib.rk_alloc_is_retired(self._h, page))


def hash_blocks_native(token_ids: Sequence[int], page_size: int,
                       max_blocks: Optional[int] = None) -> Optional[list[int]]:
    """FNV-1a block hash chain in C++; None when the library is unavailable."""
    lib = _load()
    if lib is None:
        return None
    toks = np.ascontiguousarray(token_ids, dtype=np.int32)
    cap = len(toks) // page_size if page_size else 0
    out = np.empty(max(cap, 1), dtype=np.uint64)
    n = lib.rk_hash_blocks(
        toks.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)), len(toks),
        page_size, -1 if max_blocks is None else max_blocks,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)))
    return [int(h) for h in out[:n]]


def make_page_allocator(num_pages: int):
    """Native allocator when the library loads, else the Python fallback."""
    if available():
        return NativePageAllocator(num_pages)
    from runbookai_tpu.engine.kv_cache import PageAllocator

    return PageAllocator(num_pages)
