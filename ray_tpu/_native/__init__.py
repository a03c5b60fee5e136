"""Native (C++) components, built on demand with the system toolchain."""

from __future__ import annotations

import glob
import hashlib
import os
import subprocess
import threading

_build_lock = threading.Lock()
_HERE = os.path.dirname(os.path.abspath(__file__))


def lib_path(name: str) -> str:
    """The library for <name>.cpp as it is now: the file name carries a digest of
    the source, so a library built from other source (an older checkout, a file
    copied with a fresh mtime) is never the one that gets loaded."""
    with open(os.path.join(_HERE, f"{name}.cpp"), "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    return os.path.join(_HERE, f"lib{name}-{digest}.so")


def ensure_built(name: str) -> str | None:
    """Compile <name>.cpp unless the library for exactly this source exists; returns
    the path, or None if the toolchain is unavailable or fails (callers fall back
    to Python, and report which store they run: `backend` in the store's stats)."""
    src = os.path.join(_HERE, f"{name}.cpp")
    out = lib_path(name)
    with _build_lock:
        if os.path.exists(out):
            return out
        tmp = out + f".tmp{os.getpid()}"
        try:
            subprocess.run(
                ["g++", "-O2", "-std=c++17", "-shared", "-fPIC", src, "-o", tmp,
                 "-lpthread", "-lrt"],
                check=True, capture_output=True, timeout=120,
            )
        except (OSError, subprocess.SubprocessError):
            return None
        os.replace(tmp, out)
        for stale in glob.glob(os.path.join(_HERE, f"lib{name}*.so")):
            if stale != out:
                os.remove(stale)
        return out
