"""Builds the native runtime library on demand (no pip-installable artifacts).

The library's file name carries a hash of its sources, so it is rebuilt
exactly when their content changes: a copied or freshly checked-out tree has
arbitrary mtimes and no library at all (``*.so`` is git-ignored).  The repo
stays source-only and any machine with g++ self-bootstraps on import.
"""

from __future__ import annotations

import glob
import hashlib
import os
import subprocess
import threading

_DIR = os.path.dirname(os.path.abspath(__file__))
_SOURCES = ["object_store.cc"]
_lock = threading.Lock()


def ensure_built() -> str:
    with _lock:
        srcs = [os.path.join(_DIR, s) for s in _SOURCES]
        digest = hashlib.sha256()
        for s in srcs:
            with open(s, "rb") as f:
                digest.update(f.read())
        lib = os.path.join(
            _DIR, f"libray_tpu_native.{digest.hexdigest()[:16]}.so")
        if os.path.exists(lib):
            return lib
        tmp = lib + f".tmp{os.getpid()}"
        cmd = [
            "g++", "-O2", "-shared", "-fPIC", "-std=c++17",
            "-o", tmp, *srcs, "-lpthread", "-lrt",
        ]
        subprocess.run(cmd, check=True, capture_output=True)
        os.replace(tmp, lib)
        for stale in glob.glob(os.path.join(_DIR, "libray_tpu_native*.so")):
            if stale != lib:
                try:
                    os.unlink(stale)
                except OSError:
                    pass    # another process removed it first
        return lib
