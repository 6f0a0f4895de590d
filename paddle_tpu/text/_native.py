"""Build + bind the native tokenizer core (ctypes, no pybind11).

Compiles _fast_tokenizer.c with the system compiler on first use and
keeps the .so next to the source (git ignores `*.so`), keyed by the
source hash (atomic publish, safe for concurrent builders). Nothing
outside the checkout is read or written. Import never fails:
callers check `available()` and fall back to the pure-Python path.
"""
from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import sys
import tempfile

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "_fast_tokenizer.c")
# built inside the checkout, from the tracked source. The filename is
# keyed by the SOURCE HASH so a stale binary is never loaded after the
# source changes.
_CACHE = _DIR


def _so_path():
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    return os.path.join(_CACHE, f"_fast_tokenizer_{digest}.so")

_lib = None
_err: str | None = None


def _build(so_path):
    try:
        os.makedirs(_CACHE, exist_ok=True)
    except OSError as e:
        return str(e)
    # build to a private temp file, then atomically publish: concurrent
    # first-use builders (pytest-xdist workers) never load a half-
    # written binary
    fd, tmp = tempfile.mkstemp(suffix=".so", dir=_CACHE)
    os.close(fd)
    err = "no compiler found"
    for cc in ("cc", "gcc", "clang"):
        try:
            r = subprocess.run(
                [cc, "-O2", "-shared", "-fPIC", _SRC, "-o", tmp],
                capture_output=True, text=True, timeout=120)
            if r.returncode == 0:
                os.replace(tmp, so_path)
                return None
            err = r.stderr
        except (OSError, subprocess.TimeoutExpired) as e:
            err = str(e)
    try:
        os.unlink(tmp)
    except OSError:
        pass
    return err


def _load():
    global _lib, _err
    if _lib is not None or _err is not None:
        return _lib
    try:
        so = _so_path()
        if not os.path.exists(so):
            err = _build(so)
            if err is not None:
                _err = err
                return None
        lib = ctypes.CDLL(so)
        lib.vocab_new.restype = ctypes.c_void_p
        lib.vocab_new.argtypes = [ctypes.c_size_t]
        lib.vocab_free.argtypes = [ctypes.c_void_p]
        lib.vocab_put.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                  ctypes.c_int32]
        lib.vocab_get.restype = ctypes.c_int32
        lib.vocab_get.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        lib.tokenizer_encode.restype = ctypes.c_int
        lib.tokenizer_encode.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int, ctypes.c_int,
            ctypes.c_int32, ctypes.POINTER(ctypes.c_int32), ctypes.c_int]
        lib.tokenizer_encode_batch.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p,
            ctypes.POINTER(ctypes.c_int64), ctypes.c_int, ctypes.c_int,
            ctypes.c_int32, ctypes.c_int32, ctypes.c_int32,
            ctypes.c_int32, ctypes.c_int,
            ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_int32)]
        _lib = lib
    except OSError as e:
        _err = str(e)
    return _lib


def available() -> bool:
    return _load() is not None


def build_error():
    _load()
    return _err
