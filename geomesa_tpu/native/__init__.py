"""Native (C++) runtime components, loaded via ctypes.

The reference is 100% JVM, so "native" there means external Java libs
(Kryo, Arrow, CQEngine — SURVEY.md top note); here the host-side
byte-wrangling hot paths are real C++ compiled on demand with g++ and
loaded with ctypes (no pybind11 in this image). Every native entry
point has a pure-numpy fallback so the framework works without a
toolchain; `load()` returns None when compilation is impossible.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "src")
_BUILD = os.path.join(_DIR, "_build")

_lock = threading.Lock()
_cache: dict = {}

_SOURCES = ["feature_codec.cpp", "zrange.cpp", "zencode.cpp",
            "zsort.cpp", "zbuild.cpp", "mask.cpp"]


def _source_files() -> list:
    return [os.path.join(_SRC, s) for s in _SOURCES
            if os.path.exists(os.path.join(_SRC, s))]


def _digest(paths) -> str:
    h = hashlib.sha256()
    for p in paths:
        with open(p, "rb") as f:
            h.update(f.read())
    return h.hexdigest()[:16]


def load() -> "ctypes.CDLL | None":
    """Compile (if needed) and load the native library; None on failure."""
    with _lock:
        if "lib" in _cache:
            return _cache["lib"]
        lib = _build_and_load()
        _cache["lib"] = lib
        return lib


def symbols(signatures: dict) -> "ctypes.CDLL | None":
    """Load the library and configure the given symbols, or None when
    the library or any symbol is unavailable.

    ``signatures`` maps symbol name -> (restype, argtypes). The single
    probe point for every native fast path (zranges/zencode/zsort/...)."""
    lib = load()
    if lib is None:
        return None
    for name, (restype, argtypes) in signatures.items():
        fn = getattr(lib, name, None)
        if fn is None:
            return None
        fn.restype = restype
        fn.argtypes = argtypes
    return lib


def _build_and_load():
    if os.environ.get("GEOMESA_TPU_NO_NATIVE"):
        return None
    srcs = _source_files()
    if not srcs:
        return None
    so = os.path.join(_BUILD, f"libgeomesa_{_digest(srcs)}.so")
    if not os.path.exists(so):
        os.makedirs(_BUILD, exist_ok=True)
        tmp = so + f".tmp{os.getpid()}"
        cmd = ["g++", "-O3", "-shared", "-fPIC", "-std=c++17",
               "-pthread", "-o", tmp] + srcs
        try:
            subprocess.run(cmd, check=True, capture_output=True, timeout=120)
            os.replace(tmp, so)
        except (OSError, subprocess.SubprocessError):
            return None
    try:
        return ctypes.CDLL(so)
    except OSError:
        return None
