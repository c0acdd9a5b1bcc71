"""Native (C++) host runtime components, bound via ctypes.

The device compute path is JAX/XLA/Pallas; the host data plane around
it is native where it matters.  First component: the wordlist
loader/packer (wordlist.cpp) that turns line files into the fixed-width
tables the device consumes at memory bandwidth instead of a Python
per-line loop.

The shared library is compiled on first use with the system compiler
and cached next to the sources, under a name made from the SOURCE'S
CONTENT (``libdprf_native-<sha256[:12]>.so``, git-ignored).  A copy
of the disk and a fresh checkout of git therefore run the same code:
a library built from other source has another name and is never
loaded, whatever its mtime says.  Everything degrades gracefully: if
no compiler is available the callers fall back to the pure-Python
implementations.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import tempfile
from typing import Optional

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "wordlist.cpp")

_lib: Optional[ctypes.CDLL] = None
_tried = False


def _lib_path() -> str:
    """The library's path for the source as it is on disk now."""
    with open(_SRC, "rb") as fh:
        digest = hashlib.sha256(fh.read()).hexdigest()[:12]
    return os.path.join(_DIR, f"libdprf_native-{digest}.so")


def _compile() -> Optional[str]:
    """Build the shared library for this source unless it is already
    there; returns its path or None."""
    try:
        lib = _lib_path()
        if os.path.exists(lib):
            return lib
        for cc in ("c++", "g++", "cc", "gcc"):
            # build to a temp name then rename: concurrent importers
            # must never dlopen a half-written .so
            fd, tmp = tempfile.mkstemp(suffix=".so", dir=_DIR)
            os.close(fd)
            try:
                res = subprocess.run(
                    [cc, "-O3", "-shared", "-fPIC", "-o", tmp, _SRC],
                    capture_output=True, timeout=120)
                if res.returncode == 0:
                    os.replace(tmp, lib)
                    return lib
            except (OSError, subprocess.TimeoutExpired):
                continue
            finally:
                if os.path.exists(tmp):
                    os.unlink(tmp)
    except OSError:
        pass
    return None


def load() -> Optional[ctypes.CDLL]:
    """The bound library, or None if native support is unavailable."""
    global _lib, _tried
    if _lib is not None or _tried:
        return _lib
    _tried = True
    from dprf_tpu.utils import env as envreg
    if not envreg.get_bool("DPRF_NATIVE"):
        return None
    path = _compile()
    if path is None:
        return None
    try:
        lib = ctypes.CDLL(path)
    except OSError:
        return None
    lib.dprf_wordlist_scan.restype = ctypes.c_int
    lib.dprf_wordlist_scan.argtypes = [
        ctypes.c_char_p, ctypes.c_int32,
        ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_int32)]
    lib.dprf_wordlist_pack.restype = ctypes.c_int64
    lib.dprf_wordlist_pack.argtypes = [
        ctypes.c_char_p, ctypes.c_int32,
        ctypes.POINTER(ctypes.c_uint8), ctypes.c_int64,
        ctypes.POINTER(ctypes.c_int32), ctypes.c_int64]
    _lib = lib
    return _lib


def load_words_packed(path: str, max_len: int):
    """Native loader: file -> (uint8[N, max_len] zero-padded rows,
    int32[N] lengths, n_skipped).  None if native is unavailable or the
    file can't be read natively (caller falls back to Python)."""
    lib = load()
    if lib is None:
        return None
    n_words = ctypes.c_int64()
    n_skipped = ctypes.c_int64()
    max_seen = ctypes.c_int32()
    enc = os.fsencode(path)
    if lib.dprf_wordlist_scan(enc, max_len, ctypes.byref(n_words),
                              ctypes.byref(n_skipped),
                              ctypes.byref(max_seen)) != 0:
        return None
    n = n_words.value
    buf = np.zeros((max(n, 1), max_len), dtype=np.uint8)
    lens = np.zeros((max(n, 1),), dtype=np.int32)
    if n:
        wrote = lib.dprf_wordlist_pack(
            enc, max_len,
            buf.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
            buf.strides[0],
            lens.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)), n)
        if wrote != n:   # file changed between passes: be safe
            return None
    return buf[:n], lens[:n], n_skipped.value
