"""Build-on-demand for the native data-layer library (the analogue of the
reference's build-on-demand CUDA extension workflow, ``README.md:75-80`` /
``alt_cuda_corr/setup.py`` — here a plain g++ shared object, no torch
build machinery needed)."""

from __future__ import annotations

import hashlib
import os
import subprocess
import sys
import tempfile

_SRC = os.path.join(os.path.dirname(__file__), "augment.cpp")


def lib_path() -> str:
    """Where the library for THIS ``augment.cpp`` lives: inside the
    checkout (``raft_tpu/native/_build/``, git-ignored) unless
    ``RAFT_TPU_NATIVE_DIR`` places it elsewhere, and named by the
    source's content hash — a binary built from another tree's source
    has another name and can never be loaded in its place."""
    cache = os.environ.get("RAFT_TPU_NATIVE_DIR") or os.path.join(
        os.path.dirname(__file__), "_build")
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    return os.path.join(cache, f"libraft_augment_{digest}.so")


def build(force: bool = False, quiet: bool = True) -> str:
    """Compile augment.cpp → shared library; returns its path.

    Builds once per source content (see :func:`lib_path`). Raises
    ``RuntimeError`` on compiler failure (callers fall back to numpy).
    """
    out = lib_path()
    if not force and os.path.exists(out):
        return out
    os.makedirs(os.path.dirname(out), exist_ok=True)
    # write to a temp file then rename: another process may race the build
    fd, tmp = tempfile.mkstemp(dir=os.path.dirname(out), suffix=".so")
    os.close(fd)
    cmd = ["g++", "-O3", "-shared", "-fPIC", "-std=c++17", _SRC, "-o", tmp]
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              timeout=120)
    except (OSError, subprocess.TimeoutExpired) as e:
        os.unlink(tmp)
        raise RuntimeError(f"native build failed to launch: {e}") from e
    if proc.returncode != 0:
        os.unlink(tmp)
        raise RuntimeError(f"native build failed:\n{proc.stderr}")
    os.replace(tmp, out)
    if not quiet:
        print(f"built {out}", file=sys.stderr)
    return out
