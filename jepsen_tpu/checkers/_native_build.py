"""Shared build/load scaffolding for the on-demand C++ helpers under
``native/`` (used by :mod:`.wgl_native` and :mod:`.preproc_native`).

Each helper is one translation unit compiled with g++ into
``jepsen_tpu/_build/lib*-<key>.so`` the first time it is needed, where
``<key>`` hashes the source and the compiler flags: a library built
from another revision of the source (a copied ``_build/`` directory,
whatever its mtimes) is never loaded. Callers fall back to their
pure-Python paths when the toolchain is unavailable.
"""
from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import Callable, Optional

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "native")
_BUILD_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "_build")
_FLAGS = ("-O2", "-std=c++17", "-shared", "-fPIC")


class NativeLib:
    """One lazily-built shared library.

    ``declare(lib)`` runs once after loading to set ctypes
    restype/argtypes. Build failures are cached; :meth:`load` then
    returns None forever (callers keep their Python fallback).
    """

    def __init__(self, src_name: str, so_name: str,
                 declare: Callable[[ctypes.CDLL], None]) -> None:
        self._src = os.path.join(_NATIVE_DIR, src_name)
        self._so_name = so_name
        self._declare = declare
        self._lock = threading.Lock()
        self._lib: Optional[ctypes.CDLL] = None
        self.error: Optional[str] = None

    def _so_path(self) -> str:
        """The library's path, keyed by a hash of its source and flags."""
        with open(self._src, "rb") as f:
            key = hashlib.sha256(f.read() + " ".join(_FLAGS).encode())
        stem, ext = os.path.splitext(self._so_name)
        return os.path.join(_BUILD_DIR,
                            f"{stem}-{key.hexdigest()[:16]}{ext}")

    def _build(self) -> Optional[str]:
        try:
            self._so = self._so_path()
            if os.path.exists(self._so):
                return None
            os.makedirs(_BUILD_DIR, exist_ok=True)
            # per-process tmp name: concurrent builders each write their
            # own file and the os.replace install stays atomic
            tmp = f"{self._so}.{os.getpid()}.tmp"
            p = subprocess.run(
                ["g++", *_FLAGS, "-o", tmp, self._src],
                capture_output=True, text=True, timeout=120)
            if p.returncode != 0:
                return f"g++ failed: {p.stderr[:500]}"
            os.replace(tmp, self._so)
            return None
        # jtlint: ok fallback — the probe RETURNS the error string; the chain surfaces it as engine.skipped
        except FileNotFoundError:
            return "g++ not found"
        # jtlint: ok fallback — the probe RETURNS the error string; the chain surfaces it as engine.skipped
        except Exception as e:                          # noqa: BLE001
            return f"{type(e).__name__}: {e}"

    def load(self) -> Optional[ctypes.CDLL]:
        with self._lock:
            if self._lib is not None or self.error is not None:
                return self._lib
            err = self._build()
            if err is not None:
                self.error = err
                return None
            lib = ctypes.CDLL(self._so)
            self._declare(lib)
            self._lib = lib
            return self._lib

    def available(self) -> bool:
        return self.load() is not None

    def build_error(self) -> Optional[str]:
        """Why the library is unavailable (None once it loaded)."""
        self.load()
        return self.error
