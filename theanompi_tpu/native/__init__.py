"""Native (C) host-runtime components, loaded via ctypes.

The TPU compute path is jax/XLA/pallas; the *host* runtime around it —
here the input pipeline's per-image crop/mirror gather, the one loader
step that can't vectorize in numpy — is native C, compiled on first use
with the system compiler into ``_build/`` next to this file.  The binary
is named after the hash of ``augment.c``, so only a library built from
exactly the committed source is ever loaded (``_build/`` is git-ignored
but travels with a copied tree).  Without a compiler everything runs on
the numpy reference implementation (``lib() -> None``), which stays the
source of truth the C path is tested against; :func:`crop_impl` says
which one a run used.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "augment.c")


def _so_path() -> str:
    with open(_SRC, "rb") as f:
        digest = hashlib.sha1(f.read()).hexdigest()[:12]
    return os.path.join(_DIR, "_build", f"libaugment-{digest}.so")


_lib = None
_tried = False


_build_lock = __import__("threading").Lock()


def lib():
    """The loaded native library, building it on first call; None if the
    build fails (no compiler, missing source in a wheel, read-only tree,
    hung compiler, ...) — callers always have the numpy fallback."""
    global _lib, _tried
    with _build_lock:  # threads: prefetch daemons may race the first call
        if _tried:
            return _lib
        _tried = True
        try:
            _lib = _build_and_load()
        except Exception:  # lint: swallow-ok — optional native fast path
            _lib = None
        return _lib


def available() -> bool:
    """Whether the native kernel is loadable (builds on first call)."""
    return lib() is not None


def crop_impl() -> str:
    """``"c"`` or ``"numpy"``: which crop/mirror implementation this
    process runs (entry points print it)."""
    return "c" if available() else "numpy"


def _build_and_load():
    so = _so_path()
    if not os.path.exists(so):
        os.makedirs(os.path.dirname(so), exist_ok=True)
        # build to a per-process temp name, then atomic rename: concurrent
        # PROCESSES (multi-worker launch) must never CDLL a half-written .so
        tmp = f"{so}.{os.getpid()}.tmp"
        for cc in ("cc", "gcc", "clang"):
            try:
                subprocess.run(
                    [cc, "-O3", "-shared", "-fPIC", _SRC, "-o", tmp],
                    check=True, capture_output=True, timeout=120,
                )
                os.replace(tmp, so)
                break
            except (FileNotFoundError, subprocess.CalledProcessError,
                    subprocess.TimeoutExpired):
                continue
        else:
            return None
    return _load(so)


def _load(path):
    handle = ctypes.CDLL(path)
    handle.crop_mirror_batch.argtypes = [
        ctypes.c_char_p, ctypes.c_char_p,
        ctypes.c_long, ctypes.c_long, ctypes.c_long, ctypes.c_long,
        ctypes.c_long, ctypes.c_long, ctypes.c_long,
        np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS"),
        np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS"),
        np.ctypeslib.ndpointer(np.uint8, flags="C_CONTIGUOUS"),
    ]
    handle.crop_mirror_batch.restype = None
    return handle


def crop_mirror_batch(src: np.ndarray, out_h: int, out_w: int,
                      ys: np.ndarray, xs: np.ndarray,
                      flips: np.ndarray) -> np.ndarray | None:
    """Native per-image crop+mirror; -> result, or None when unavailable
    (caller falls back to the numpy loop).

    ``src``: [N, H, W, C] any fixed-size dtype; ``ys``/``xs``: per-image
    top-left offsets; ``flips``: per-image horizontal-mirror booleans.
    """
    handle = lib()
    if handle is None:
        return None
    src = np.ascontiguousarray(src)
    n, h, w, c = src.shape
    out = np.empty((n, out_h, out_w, c), src.dtype)
    handle.crop_mirror_batch(
        src.ctypes.data_as(ctypes.c_char_p),
        out.ctypes.data_as(ctypes.c_char_p),
        n, h, w, c, src.dtype.itemsize, out_h, out_w,
        np.ascontiguousarray(ys, np.int64),
        np.ascontiguousarray(xs, np.int64),
        np.ascontiguousarray(flips, np.uint8),
    )
    return out
