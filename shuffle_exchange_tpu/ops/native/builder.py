"""Lazy JIT build of the native runtime library.

The reference compiles its native extensions on demand through accelerator-
dispatched op builders (SURVEY.md §2.13, ``op_builder/`` — absent from the
snapshot but enumerable from imports). Same capability here, our shape: one
C++ library (``csrc/``) built with g++ at first use, cached next to the
sources (or in ``SXT_NATIVE_CACHE``), loaded via ctypes. Everything that
uses it degrades gracefully to a NumPy fallback when no toolchain exists.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from typing import Optional

from ...utils.logging import logger

_LOCK = threading.Lock()
_LIB: Optional[ctypes.CDLL] = None
_TRIED = False

_REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "..", ".."))
CSRC_DIR = os.path.join(_REPO_ROOT, "csrc")


def _build_dir() -> str:
    cache = os.environ.get("SXT_NATIVE_CACHE")
    if cache:
        os.makedirs(cache, exist_ok=True)
        return cache
    return CSRC_DIR


_SOURCES = ("aio.cc", "cpu_optim.cc", "packbits.cc", "sxt_native.h")
# No -march=native: the library is built where it is first used but may be
# loaded elsewhere (a copy of the tree on another machine's CPU).
_CXX = ("g++", "-O3", "-std=c++17", "-fPIC", "-Wall", "-fopenmp", "-shared")


def _compile() -> Optional[str]:
    """Path of the built library, building it if no build of THESE sources
    with THESE flags exists. The library is named by the digest of both, so
    staleness is decided by content: copying the tree may reset every mtime,
    and a library left behind by other sources is simply not this one."""
    import hashlib

    paths = [os.path.join(CSRC_DIR, f) for f in _SOURCES]
    if not all(os.path.exists(p) for p in paths):
        return None
    digest = hashlib.sha256(" ".join(_CXX).encode())
    for path in paths:
        with open(path, "rb") as f:
            digest.update(f.read())
    out_dir = _build_dir()
    so_path = os.path.join(out_dir,
                           f"libsxt_native.{digest.hexdigest()[:16]}.so")
    if os.path.exists(so_path):
        return so_path
    # Build to a per-PID temp name and os.rename into place: rename is atomic
    # on the same filesystem, so concurrent processes (multiple local ranks,
    # parallel test runs, a shared NFS cache) never dlopen a half-written .so
    # or clobber each other mid-build.
    tmp_path = os.path.join(out_dir, f".libsxt_native.{os.getpid()}.tmp.so")
    cmd = list(_CXX) + ["-o", tmp_path] + [p for p in paths if p.endswith(".cc")]
    try:
        res = subprocess.run(cmd, capture_output=True, text=True, timeout=300)
    except (OSError, subprocess.TimeoutExpired) as e:
        logger.warning(f"native build failed to launch: {e}")
        return None
    if res.returncode != 0:
        logger.warning(f"native build failed ({' '.join(cmd[:2])}...): "
                       f"{res.stderr[-500:]}")
        if os.path.exists(tmp_path):
            os.remove(tmp_path)
        return None
    try:
        os.rename(tmp_path, so_path)
    except OSError as e:
        logger.warning(f"native build rename failed: {e}")
        return so_path if os.path.exists(so_path) else None
    return so_path


def _bind(lib: ctypes.CDLL) -> ctypes.CDLL:
    c = ctypes
    p, u8p, u16p, fp = c.c_void_p, c.POINTER(c.c_uint8), c.POINTER(c.c_uint16), c.POINTER(c.c_float)
    lib.sxt_native_version.restype = c.c_int
    lib.sxt_aio_create.restype = p
    lib.sxt_aio_create.argtypes = [c.c_int, c.c_int]
    lib.sxt_aio_destroy.argtypes = [p]
    lib.sxt_aio_submit_read.restype = c.c_int64
    lib.sxt_aio_submit_read.argtypes = [p, c.c_char_p, c.c_void_p, c.c_size_t, c.c_size_t]
    lib.sxt_aio_submit_write.restype = c.c_int64
    lib.sxt_aio_submit_write.argtypes = [p, c.c_char_p, c.c_void_p, c.c_size_t, c.c_size_t]
    lib.sxt_aio_wait.restype = c.c_int64
    lib.sxt_aio_wait.argtypes = [p, c.c_int64]
    lib.sxt_aio_wait_all.restype = c.c_int64
    lib.sxt_aio_wait_all.argtypes = [p]
    lib.sxt_aio_poll.restype = c.c_int
    lib.sxt_aio_poll.argtypes = [p, c.c_int64]
    lib.sxt_aligned_alloc.restype = p
    lib.sxt_aligned_alloc.argtypes = [c.c_size_t, c.c_size_t]
    lib.sxt_aligned_free.argtypes = [p]
    lib.sxt_adam_step.argtypes = [fp, fp, fp, fp, c.c_size_t, c.c_float, c.c_float,
                                  c.c_float, c.c_float, c.c_float, c.c_int, c.c_int, c.c_int, u16p]
    lib.sxt_adagrad_step.argtypes = [fp, fp, fp, c.c_size_t, c.c_float, c.c_float, c.c_float, u16p]
    lib.sxt_lion_step.argtypes = [fp, fp, fp, c.c_size_t, c.c_float, c.c_float, c.c_float, c.c_float, u16p]
    lib.sxt_lamb_step.argtypes = [fp, fp, fp, fp, c.c_size_t, c.c_float, c.c_float,
                                  c.c_float, c.c_float, c.c_float, c.c_int, c.c_int, u16p]
    lib.sxt_packbits.restype = c.c_size_t
    lib.sxt_packbits.argtypes = [fp, u8p, c.c_size_t]
    lib.sxt_unpackbits.argtypes = [u8p, fp, c.c_size_t, c.c_float]
    return lib


def load_native() -> Optional[ctypes.CDLL]:
    """The library, building it on first call; None if unavailable."""
    global _LIB, _TRIED
    with _LOCK:
        if _LIB is not None or _TRIED:
            return _LIB
        _TRIED = True
        if os.environ.get("SXT_DISABLE_NATIVE"):
            return None
        so_path = _compile()
        if so_path is None:
            logger.warning("libsxt_native unavailable; native-backed paths fall back to NumPy")
            return None
        try:
            lib = _bind(ctypes.CDLL(so_path))
        except OSError as e:
            logger.warning(f"failed to load {so_path}: {e}")
            return None
        if lib.sxt_native_version() != 1:
            logger.warning("libsxt_native ABI mismatch; ignoring")
            return None
        _LIB = lib
        return _LIB


def native_available() -> bool:
    return load_native() is not None
