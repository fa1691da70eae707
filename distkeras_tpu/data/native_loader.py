"""ctypes binding for the native (C++) data-plane kernels.

Builds ``distkeras_tpu/native/loader.cc`` with the system g++ on first use and
caches the shared object next to the source, named by the source's content
hash — a build product copied from elsewhere (mtimes do not survive a copy) or
left over from an older ``loader.cc`` can never be mistaken for a current one.
Every entry point degrades to a numpy fallback when the toolchain or the .so is
unavailable, so the framework never *requires* the native path — it's a
throughput upgrade, not a dependency (mirroring how the reference leaned on the
Spark JVM without owning it). Which path serves, and the compiler's message
when the build failed, is :func:`served_by`.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import Optional

import numpy as np

from distkeras_tpu.runtime import config

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                           "native")
_SRC = os.path.join(_NATIVE_DIR, "loader.cc")

_lib = None
_why_numpy: Optional[str] = None  # set when get_lib() settled on None
_lock = threading.Lock()
_DISABLED = config.env_bool("DKTPU_NO_NATIVE")

# Must match dk_abi_version() in native/loader.cc. Bump both on any signature
# change; a mismatch (.cc edited without this constant) disables the native
# path rather than calling through a wrong prototype.
_ABI_VERSION = 2


def _so_path() -> str:
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    return os.path.join(_NATIVE_DIR, f"_loader-{digest}.so")


def _build(so: str) -> Optional[str]:
    """Compile ``loader.cc`` into ``so``; returns None, or why it failed."""
    tmp = f"{so}.{os.getpid()}.tmp"  # concurrent first users never see a torn .so
    cmd = ["g++", "-O3", "-shared", "-fPIC", "-std=c++17", "-o", tmp, _SRC,
           "-lpthread"]
    try:
        subprocess.run(cmd, check=True, capture_output=True, text=True,
                       timeout=120)
        os.replace(tmp, so)
        return None
    except subprocess.CalledProcessError as e:
        return f"g++ exited {e.returncode}: {e.stderr.strip()}"
    except (OSError, subprocess.TimeoutExpired) as e:
        return f"g++ did not run: {e}"


def _load() -> tuple:
    """(lib, None) or (None, why)."""
    so = _so_path()
    if not os.path.exists(so):
        err = _build(so)
        if err is not None:
            return None, err
    try:
        lib = ctypes.CDLL(so)
    except OSError as e:
        return None, f"cannot load {so}: {e}"
    try:
        lib.dk_abi_version.restype = ctypes.c_int
        lib.dk_abi_version.argtypes = []
        abi = lib.dk_abi_version()
        if abi != _ABI_VERSION:
            return None, f"ABI {abi} in loader.cc, {_ABI_VERSION} expected"
    except AttributeError:
        return None, "loader.cc exports no dk_abi_version()"
    lib.dk_gather_rows.restype = ctypes.c_int
    lib.dk_gather_rows.argtypes = [
        ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64,
        ctypes.c_void_p, ctypes.c_int64, ctypes.c_void_p, ctypes.c_int,
    ]
    lib.dk_scale_f32.restype = None
    lib.dk_scale_f32.argtypes = [
        ctypes.c_void_p, ctypes.c_int64, ctypes.c_float, ctypes.c_float,
        ctypes.c_float, ctypes.c_void_p, ctypes.c_int,
    ]
    return lib, None


def get_lib():
    """Load (building if needed) the native library, or None."""
    global _lib, _why_numpy
    if _DISABLED:
        return None
    if _lib is not None or _why_numpy is not None:
        return _lib
    with _lock:
        if _lib is None and _why_numpy is None:
            _lib, _why_numpy = _load()
        return _lib


def served_by() -> tuple:
    """``("native", None)`` or ``("numpy", why)`` — which path serves
    :func:`gather_rows`/:func:`scale_f32` in this process, with g++'s own
    message when the build failed."""
    if _DISABLED:
        return "numpy", "DKTPU_NO_NATIVE is set"
    return ("native", None) if get_lib() is not None else ("numpy", _why_numpy)


def num_threads() -> int:
    return max(1, (os.cpu_count() or 1))


def gather_rows(src: np.ndarray, idx: np.ndarray) -> np.ndarray:
    """``src[idx]`` with the index array applied to axis 0.

    ``idx`` may have any shape; the result has shape ``idx.shape + src.shape[1:]``.
    Uses the native threaded gather when available, numpy fancy indexing
    otherwise (bit-identical results).
    """
    from distkeras_tpu import telemetry

    tele = telemetry.get()
    lib = get_lib()
    if lib is None or not src.flags.c_contiguous or src.dtype == object:
        # Which path served the gather matters for perf triage: a silent
        # fallback (toolchain missing, non-contiguous column) looks like a
        # data-plane regression otherwise.
        tele.counter("native.gather_fallback_calls").add(1)
        return src[idx]
    flat_idx = np.ascontiguousarray(idx.reshape(-1), np.int64)
    row_bytes = int(src.dtype.itemsize * np.prod(src.shape[1:], dtype=np.int64))
    if row_bytes == 0:
        return src[idx]
    out = np.empty((flat_idx.size,) + src.shape[1:], src.dtype)
    with tele.span("native.gather"):
        rc = lib.dk_gather_rows(
            src.ctypes.data_as(ctypes.c_void_p), src.shape[0], row_bytes,
            flat_idx.ctypes.data_as(ctypes.c_void_p), flat_idx.size,
            out.ctypes.data_as(ctypes.c_void_p), num_threads(),
        )
    if rc != 0:
        raise IndexError("gather index out of range")
    tele.counter("native.gather_calls").add(1)
    tele.counter("native.gather_bytes").add(float(out.nbytes))
    return out.reshape(idx.shape + src.shape[1:])


def scale_f32(src: np.ndarray, offset: float, scale: float,
              bias: float = 0.0) -> np.ndarray:
    """``(src - offset) * scale + bias`` for float32 arrays (threaded when native).

    ``bias`` is applied separately rather than folded into ``offset`` so that a
    huge ``scale`` (degenerate input range) can't cancel it away in float32.
    """
    lib = get_lib()
    if lib is None or src.dtype != np.float32 or not src.flags.c_contiguous:
        return (((src - np.float32(offset)) * np.float32(scale))
                + np.float32(bias)).astype(np.float32)
    out = np.empty_like(src)
    lib.dk_scale_f32(
        src.ctypes.data_as(ctypes.c_void_p), src.size,
        ctypes.c_float(offset), ctypes.c_float(scale), ctypes.c_float(bias),
        out.ctypes.data_as(ctypes.c_void_p), num_threads(),
    )
    return out
