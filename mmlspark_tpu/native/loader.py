"""Build-and-load machinery for the bundled C++ runtime components.

Parity: `core/env/src/main/scala/NativeLoader.java:28,48-62` — the
reference extracts named ``.so``s (plus a ``NATIVE_MANIFEST`` of
dependencies) from jar resources into a temp dir and ``System.load``s
them, preferring ``java.library.path``. The TPU framework instead ships
C++ *sources* inside the package and compiles them on first use:

search order for ``load_library_by_name(name)``:
1. ``$MMLSPARK_TPU_NATIVE_DIR/lib<name>.so`` (operator-provided prebuilt,
   the ``java.library.path`` analogue),
2. the package build cache (``native/_build``), keyed on a hash of
   the sources and the compiler flags — a binary built from other
   sources (a stale one copied along with a checkout, whatever its
   mtime) has another name and is never loaded,
3. fresh compile via ``g++`` (declared in ``_SOURCES``).
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import Dict, List, Optional

_SRC_DIR = os.path.join(os.path.dirname(__file__), "src")
_BUILD_DIR = os.path.join(os.path.dirname(__file__), "_build")

# name -> (sources, extra link flags); the NATIVE_MANIFEST analogue
_SOURCES: Dict[str, List[str]] = {
    "mmlbinary": ["binary_reader.cpp"],
}
_LINK_FLAGS: Dict[str, List[str]] = {
    "mmlbinary": ["-lz"],
}
_COMPILE_FLAGS = ["-O2", "-std=c++17", "-shared", "-fPIC", "-pthread"]

_lock = threading.Lock()
# name -> CDLL, or the Exception a previous attempt raised (negative cache:
# a missing toolchain must not re-run g++ on every read)
_cache: Dict[str, object] = {}


class NativeLoader:
    """Loads (building if needed) a named native library."""

    @staticmethod
    def load_library_by_name(name: str) -> ctypes.CDLL:
        with _lock:
            hit = _cache.get(name)
            if isinstance(hit, ctypes.CDLL):
                return hit
            if isinstance(hit, Exception):
                raise hit
            try:
                lib = ctypes.CDLL(_find_or_build(name))
            except Exception as e:
                _cache[name] = e
                raise
            _cache[name] = lib
            return lib


def _find_or_build(name: str) -> str:
    so_name = f"lib{name}.so"
    override = os.environ.get("MMLSPARK_TPU_NATIVE_DIR")
    if override:
        cand = os.path.join(override, so_name)
        if os.path.exists(cand):
            return cand
    if name not in _SOURCES:
        raise FileNotFoundError(f"unknown native library {name!r}")
    sources = [os.path.join(_SRC_DIR, s) for s in _SOURCES[name]]
    link_flags = _LINK_FLAGS.get(name, [])
    key = hashlib.sha256("\0".join(_COMPILE_FLAGS + link_flags).encode())
    for src in sources:
        with open(src, "rb") as f:
            key.update(f.read())
    built = os.path.join(_BUILD_DIR,
                         f"lib{name}.{key.hexdigest()[:16]}.so")
    if os.path.exists(built):
        return built
    os.makedirs(_BUILD_DIR, exist_ok=True)
    # compile to a private temp name, then atomically publish: concurrent
    # builders (pytest-xdist, two cold-starting services) must never see
    # a half-written .so
    tmp = f"{built}.tmp.{os.getpid()}"
    cmd = ["g++", *_COMPILE_FLAGS, *sources, "-o", tmp, *link_flags]
    proc = subprocess.run(cmd, capture_output=True, text=True)
    if proc.returncode != 0:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise RuntimeError(
            f"native build of {name} failed:\n{proc.stderr[-2000:]}")
    os.replace(tmp, built)
    return built


def native_available(name: str = "mmlbinary") -> bool:
    """True when the named native library can be loaded (builds on demand)."""
    try:
        NativeLoader.load_library_by_name(name)
        return True
    except Exception:
        return False
