"""Compile-on-first-use build for the native (C++) runtime components.

No pip/pybind11 in the image, so bindings are ctypes over plain C ABIs and
the shared objects are built lazily with g++ into ``native/_build/``. The
file name carries a hash of the sources and flags, so a library is reused
exactly when it was built from these bytes: a copied tree with reordered
mtimes, or a stale ``.so`` left by another checkout, is never loaded.
"""

import hashlib
import os
import shutil
import subprocess
import threading

_HERE = os.path.dirname(os.path.abspath(__file__))
_BUILD = os.path.join(_HERE, "_build")
_LOCK = threading.Lock()


def build_library(name: str, sources, extra_flags=()) -> str:
    """Build ``lib<name>-<hash>.so`` from ``sources`` (paths relative to
    native/) unless that exact build exists; returns the .so path."""
    srcs = [os.path.join(_HERE, s) for s in sources]
    digest = hashlib.sha256(" ".join(extra_flags).encode())
    for s in srcs:
        with open(s, "rb") as f:
            digest.update(f.read())
    out = os.path.join(_BUILD, f"lib{name}-{digest.hexdigest()[:16]}.so")
    with _LOCK:
        if os.path.exists(out):
            return out
        if shutil.which("g++") is None:
            raise RuntimeError(
                f"g++ not found: lib{name}.so is compiled from "
                f"{', '.join(sources)} on first use and needs a C++17 "
                "compiler on PATH"
            )
        os.makedirs(_BUILD, exist_ok=True)
        # pid-unique tmp + atomic replace: concurrent trainer processes on
        # one host may race to build the same library on a cold cache
        tmp = f"{out}.{os.getpid()}.tmp"
        cmd = [
            "g++",
            "-O3",
            "-std=c++17",
            "-shared",
            "-fPIC",
            "-Wall",
            *extra_flags,
            *srcs,
            "-o",
            tmp,
            "-lpthread",
        ]
        # serializing the compile IS this lock's job: concurrent callers
        # must block until the one g++ build lands, not race it
        # threadlint: disable=blocking-under-lock
        proc = subprocess.run(cmd, capture_output=True, text=True)
        if proc.returncode != 0:
            raise RuntimeError(
                f"g++ failed building lib{name}.so:\n{proc.stderr}"
            )
        os.replace(tmp, out)
    return out


def load_library(name: str, sources, extra_flags=()):
    import ctypes

    return ctypes.CDLL(build_library(name, sources, extra_flags))
