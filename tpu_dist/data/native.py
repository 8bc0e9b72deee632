"""ctypes bridge to the native C++ input pipeline (``tpu_dist/csrc``).

The reference leans on native code for its input path (torchvision's C
extensions + DataLoader worker processes, SURVEY §2.2 N7); this module is
the TPU build's equivalent: a fused gather+pad+crop+normalize over the
batch in multi-threaded C++. The shared library is built from
``csrc/pipeline.cpp`` on the machine that runs (``make`` on first use — a
no-op when the build is newer than the source), never shipped. Where it
cannot be built or loaded, the numpy implementation in
``tpu_dist.data.transforms`` runs instead — same semantics, different crop
offsets — with a warning; :func:`path_in_use` says which one a run got.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
import warnings
from typing import Optional

import numpy as np

from tpu_dist.data import transforms

_CSRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "csrc")
_SO = os.path.join(_CSRC, "build", "libtpu_dist_pipeline.so")

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False
_why_numpy = ""  # why the native path is not in use, once _load has failed


def _give_up(reason: str) -> None:
    global _why_numpy
    _why_numpy = reason
    warnings.warn(
        f"native input pipeline unavailable ({reason}); using the numpy "
        "path, which draws different crop offsets",
        RuntimeWarning, stacklevel=3,
    )


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        try:  # (re)build from source; tolerate a missing toolchain
            subprocess.run(
                ["make", "-C", _CSRC],
                check=True,
                capture_output=True,
                timeout=120,
            )
        except (OSError, subprocess.SubprocessError) as e:
            _give_up(f"build failed: {type(e).__name__}: {e}")
            return None
        try:
            lib = ctypes.CDLL(_SO)
            lib.tpu_dist_augment_batch.restype = ctypes.c_int
            lib.tpu_dist_augment_batch.argtypes = [
                ctypes.POINTER(ctypes.c_uint8),   # images
                ctypes.POINTER(ctypes.c_int64),   # indices
                ctypes.POINTER(ctypes.c_float),   # out
                ctypes.c_int64, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
                ctypes.c_int64,                   # pad
                ctypes.c_uint64,                  # seed
                ctypes.POINTER(ctypes.c_float),   # mean
                ctypes.POINTER(ctypes.c_float),   # std
                ctypes.c_int,                     # train
                ctypes.c_int,                     # n_threads
            ]
            if lib.tpu_dist_pipeline_abi_version() != 1:
                _give_up("ABI version mismatch")
                return None
            _lib = lib
        except (OSError, AttributeError) as e:
            # AttributeError: a stale/foreign .so missing our symbols
            _give_up(f"load failed: {type(e).__name__}: {e}")
            return None
        return _lib


def available() -> bool:
    return _load() is not None


def path_in_use() -> str:
    """``"native"`` or ``"numpy (<why>)"`` — which augment path this
    process runs (building/loading the library if that has not happened)."""
    return "native" if _load() is not None else f"numpy ({_why_numpy})"


def gather_augment(
    images: np.ndarray,
    indices: np.ndarray,
    *,
    seed: int,
    train: bool,
    padding: int = 4,
    mean: np.ndarray = transforms.CIFAR100_MEAN,
    std: np.ndarray = transforms.CIFAR100_STD,
    n_threads: int = 0,
) -> np.ndarray:
    """Fused ``normalize(random_crop(images[indices]))`` → f32 NHWC batch.

    Uses the C++ pipeline when built; otherwise the numpy reference path
    (identical semantics, different crop-offset RNG stream).
    """
    lib = _load()
    n = len(indices)
    _, h, w, c = images.shape
    if lib is not None:
        images = np.ascontiguousarray(images)
        idx = np.ascontiguousarray(indices, np.int64)
        out = np.empty((n, h, w, c), np.float32)
        mean32 = np.ascontiguousarray(mean, np.float32)
        std32 = np.ascontiguousarray(std, np.float32)
        rc = lib.tpu_dist_augment_batch(
            images.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
            idx.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            n, h, w, c,
            padding if train else 0,
            np.uint64(seed & 0xFFFFFFFFFFFFFFFF),
            mean32.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            std32.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            1 if train else 0,
            n_threads,
        )
        if rc == 0:
            return out
    # numpy fallback
    batch = images[indices]
    if train:
        rng = np.random.default_rng(seed)
        batch = transforms.random_crop_batch(batch, rng, padding)
    return (batch.astype(np.float32) / 255.0 - mean) / std
