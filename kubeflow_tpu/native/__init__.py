"""Loader for the framework's native (C++) components.

The reference platform leans on native dependencies for its hot paths —
Triton's C++ serving core, MLMD's C++ metadata store, NCCL/MPI rendezvous
(SURVEY.md §2.6). This package provides the TPU-native equivalents as small
C++ libraries with flat C ABIs, bound via ctypes (no pybind11 in the image).

Libraries are compiled on demand from ``native/src/*.cpp`` with the system
g++ into the git-ignored ``native/build/``, keyed by the source's content
(a checkout or a copy sets mtimes arbitrarily, so a binary is never trusted
for being newer than its source); environments without a toolchain raise
``NativeUnavailable`` and callers fall back to their pure-Python
implementations (same contract, slower queue/scheduling paths).
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import shutil
import subprocess
import tempfile
import threading

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SRC_DIR = os.path.join(_REPO_ROOT, "native", "src")
BUILD_DIR = os.path.join(_REPO_ROOT, "native", "build")

_lock = threading.Lock()
_cache: dict[str, ctypes.CDLL] = {}


class NativeUnavailable(RuntimeError):
    """No toolchain / source for the requested native library."""


def _compiler() -> str | None:
    return shutil.which("g++") or shutil.which("c++")


def build(name: str, force: bool = False) -> str:
    """Compile native/src/<name>.cpp → native/build/lib<name>-<sha>.so, where
    <sha> is a digest of the source; returns the path."""
    src = os.path.join(SRC_DIR, f"{name}.cpp")
    if not os.path.exists(src):
        raise NativeUnavailable(f"no native source {src}")
    # SURVEY.md §5.2: sanitizer presets for the native components
    # (KTPU_NATIVE_SANITIZE=thread|address|undefined). The sanitized build
    # gets its own artifact name so it never poisons (or hides behind) the
    # cached normal .so. NOTE: dlopen'ing a sanitized .so needs the runtime
    # preloaded (LD_PRELOAD=libtsan.so.2 python ...); the standalone race
    # harness is scripts/native_sanitize.sh
    san = os.environ.get("KTPU_NATIVE_SANITIZE")
    if san and san not in ("thread", "address", "undefined"):
        raise NativeUnavailable(
            f"KTPU_NATIVE_SANITIZE={san!r} (want thread|address|undefined)")
    suffix = f".{san[0]}san.so" if san else ".so"
    with open(src, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    out = os.path.join(BUILD_DIR, f"lib{name}-{digest}{suffix}")
    if not force and os.path.exists(out):
        return out
    cxx = _compiler()
    if cxx is None:
        raise NativeUnavailable("no C++ compiler on PATH")
    os.makedirs(BUILD_DIR, exist_ok=True)
    # a private temp name: with the binaries untracked, the workers of a
    # multi-process job all build on a fresh checkout at the same moment
    fd, tmp = tempfile.mkstemp(dir=BUILD_DIR, suffix=".tmp")
    os.close(fd)
    if san:
        cmd = [cxx, "-O1", "-g", f"-fsanitize={san}", "-std=c++17",
               "-shared", "-fPIC", "-pthread", src, "-o", tmp]
    else:
        cmd = [cxx, "-O2", "-std=c++17", "-shared", "-fPIC", "-pthread",
               src, "-o", tmp]
    proc = subprocess.run(cmd, capture_output=True, text=True)
    if proc.returncode != 0:
        os.unlink(tmp)
        raise NativeUnavailable(
            f"native build failed: {' '.join(cmd)}\n{proc.stderr[-2000:]}")
    os.chmod(tmp, 0o755)  # mkstemp creates 0600
    os.replace(tmp, out)  # atomic: concurrent builders race benignly
    return out


def library(name: str) -> ctypes.CDLL:
    """Load (building if needed) a native library by source name."""
    with _lock:
        if name not in _cache:
            _cache[name] = ctypes.CDLL(build(name))
        return _cache[name]


def loaded() -> list[str]:
    """Names of the native libraries this process has loaded so far."""
    with _lock:
        return sorted(_cache)


def available(name: str) -> bool:
    try:
        library(name)
        return True
    except NativeUnavailable:
        return False
