"""ctypes binding for the native C++ CIFAR-10 loader/prefetcher.

The compute path is JAX/XLA; the input pipeline around it is native C++
(``native/dataloader.cc``): parsing, per-epoch shuffling, normalization, and
batch assembly run in worker threads that prefetch ahead of the TPU step
loop.  This module builds the shared library on first use (``make -C
native``) and exposes a Python iterator; callers that can tolerate the slow
path should catch ``NativeLoaderUnavailable`` and fall back to
:func:`ddl25spring_tpu.data.cifar10.load_cifar10`'s in-memory arrays.
"""

from __future__ import annotations

import ctypes
import subprocess
import threading
from pathlib import Path

import numpy as np

_NATIVE_DIR = Path(__file__).resolve().parent.parent.parent / "native"
_LIB_NAME = "libddl25_dataloader.so"
_lock = threading.Lock()
_lib = None


class NativeLoaderUnavailable(RuntimeError):
    """Toolchain or data missing — use the numpy path instead."""


def load_native_lib(lib_name: str) -> ctypes.CDLL:
    """Build-on-demand + load for a ``native/`` shared library: shared by
    the C++ dataloader and BPE bindings so the make/CDLL/error handling
    lives once.  Raises :class:`NativeLoaderUnavailable` when the
    toolchain or artifact is unusable (callers fall back to Python)."""
    so = _NATIVE_DIR / lib_name
    if not so.exists():
        try:
            subprocess.run(
                ["make", "-C", str(_NATIVE_DIR), lib_name],
                check=True, capture_output=True, text=True,
            )
        except (OSError, subprocess.CalledProcessError) as e:
            detail = getattr(e, "stderr", "") or str(e)
            raise NativeLoaderUnavailable(
                f"building {lib_name} failed: {detail}"
            ) from e
    try:
        return ctypes.CDLL(str(so))
    except OSError as e:  # wrong arch / corrupt .so: fall back, don't crash
        raise NativeLoaderUnavailable(f"loading {so} failed: {e}") from e


def rebuild_native_libs() -> list[str]:
    """The strict path (chip runs — ``chip_smoke.py`` calls it first):
    rebuild EVERY native library from ``native/*.cc`` and
    ``native/Makefile``, the files git commits, whatever already sits in
    ``native/`` (the ``.so`` files are git-ignored build outputs and may
    be stale).  A failed build raises ``RuntimeError`` — an error, not a
    slower loader.  Returns the libraries built; :func:`load_native_lib`
    then finds them."""
    try:
        subprocess.run(
            ["make", "-B", "-C", str(_NATIVE_DIR), "all"],
            check=True, capture_output=True, text=True,
        )
    except (OSError, subprocess.CalledProcessError) as e:
        detail = getattr(e, "stderr", "") or str(e)
        raise RuntimeError(
            f"building the native libraries failed: {detail}"
        ) from e
    return sorted(p.name for p in _NATIVE_DIR.glob("*.so"))


def _load_lib():
    global _lib
    with _lock:
        if _lib is not None:
            return _lib
        lib = load_native_lib(_LIB_NAME)
        lib.dl_create.restype = ctypes.c_void_p
        lib.dl_create.argtypes = [
            ctypes.c_char_p, ctypes.c_int, ctypes.c_uint64,
            ctypes.c_int, ctypes.c_int, ctypes.c_int,
        ]
        lib.dl_error.restype = ctypes.c_char_p
        lib.dl_error.argtypes = [ctypes.c_void_p]
        lib.dl_num_samples.restype = ctypes.c_long
        lib.dl_num_samples.argtypes = [ctypes.c_void_p]
        lib.dl_next.restype = ctypes.c_long
        lib.dl_next.argtypes = [
            ctypes.c_void_p,
            ctypes.c_void_p,
            ctypes.POINTER(ctypes.c_int32),
        ]
        lib.dl_destroy.argtypes = [ctypes.c_void_p]
        _lib = lib
        return lib


class NativeCifar10Loader:
    """Infinite iterator of ``(x [B,32,32,3] float32, y [B] int32)`` batches,
    prefetched and shuffled per epoch in C++ worker threads.

    Deterministic for a given ``seed`` (per-epoch Fisher-Yates in the C++
    side); ``epoch`` property reports the epoch of the last batch yielded.

    ``normalize=False`` yields raw uint8 NHWC pixels instead of normalized
    float32 — 4x less host->device traffic; normalize on-device with
    :func:`normalize_on_device` (which XLA fuses into the train step).
    """

    def __init__(
        self,
        data_dir: str | Path,
        batch_size: int,
        seed: int = 0,
        prefetch_depth: int = 4,
        workers: int = 2,
        normalize: bool = True,
    ):
        lib = _load_lib()
        self._lib = lib
        self.normalize = normalize
        self._handle = lib.dl_create(
            str(data_dir).encode(), batch_size, seed, prefetch_depth, workers,
            int(normalize),
        )
        err = lib.dl_error(self._handle)
        if err:
            msg = err.decode()
            lib.dl_destroy(self._handle)
            self._handle = None
            raise NativeLoaderUnavailable(msg)
        self.batch_size = batch_size
        self.num_samples = lib.dl_num_samples(self._handle)
        self.epoch = 0

    def __iter__(self):
        dtype = np.float32 if self.normalize else np.uint8
        x = np.empty((self.batch_size, 32, 32, 3), dtype)
        y = np.empty((self.batch_size,), np.int32)
        xp = x.ctypes.data_as(ctypes.c_void_p)
        yp = y.ctypes.data_as(ctypes.POINTER(ctypes.c_int32))
        while True:
            epoch = self._lib.dl_next(self._handle, xp, yp)
            if epoch < 0:
                return
            self.epoch = int(epoch)
            yield x.copy(), y.copy()

    def close(self):
        if self._handle is not None:
            self._lib.dl_destroy(self._handle)
            self._handle = None

    def __del__(self):
        # no contextlib.suppress here: at interpreter teardown the module
        # globals may already be cleared, and a finalizer must not do
        # global lookups before reaching the native free
        try:  # noqa: SIM105
            self.close()
        except Exception:
            pass


def normalize_on_device(x_uint8, dtype=None):
    """Device-side CIFAR-10 normalization of raw uint8 NHWC batches (pairs
    with ``NativeCifar10Loader(normalize=False)``); inside jit XLA fuses it
    into the consuming step."""
    import jax.numpy as jnp

    from ddl25spring_tpu.data.cifar10 import MEAN, STD

    x = x_uint8.astype(dtype or jnp.float32)
    mean = jnp.asarray(MEAN, x.dtype) * 255.0
    inv = 1.0 / (jnp.asarray(STD, x.dtype) * 255.0)
    return (x - mean) * inv
