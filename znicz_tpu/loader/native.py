"""ctypes bindings for the native batch assembler.

The data-plane hot path (per-minibatch gather + normalize) runs in
``native/batch_assembler.cc`` when the shared library is available — built
on first use with g++ — and falls back to numpy transparently otherwise
(the framework stays pure-Python-runnable, like the reference's NumpyDevice
property).

Measured on this host (CIFAR-sized dataset, batch 4096): the fused
u8-gather+normalize is ~3x faster than the numpy
``data[idx].astype(f32)/255`` chain (and keeps the dataset in u8, 4x less
host RAM); the plain f32 gather is bandwidth-bound and merely matches
numpy — it exists so callers have one code path.
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
import threading
from typing import Optional

import numpy as np

_REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
_SOURCE = os.path.join(_REPO_ROOT, "native", "batch_assembler.cc")
_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False


def _build_and_load() -> Optional[ctypes.CDLL]:
    """Compile (once) and dlopen the assembler; None if unavailable."""
    global _lib, _tried
    with _lock:
        if _tried:
            return _lib
        _tried = True
        if not os.path.exists(_SOURCE):
            return None
        cache = os.environ.get(
            "ZNICZ_NATIVE_CACHE", os.path.join(_REPO_ROOT, ".native_cache")
        )
        # keyed by the source's CONTENT: a library left in the cache by
        # another checkout (or copied along with the tree) can only ever
        # be loaded for the source it was built from
        with open(_SOURCE, "rb") as f:
            digest = hashlib.sha256(f.read()).hexdigest()[:16]
        so_path = os.path.join(cache, f"libbatch_assembler-{digest}.so")
        try:
            if not os.path.exists(so_path):
                os.makedirs(cache, exist_ok=True)
                tmp = f"{so_path}.{os.getpid()}.tmp"
                subprocess.run(
                    [
                        "g++", "-O3", "-shared", "-fPIC", "-std=c++17",
                        "-o", tmp, _SOURCE, "-pthread",
                    ],
                    check=True,
                    capture_output=True,
                )
                os.replace(tmp, so_path)  # atomic: no half-written loads
            lib = ctypes.CDLL(so_path)
        except (OSError, subprocess.SubprocessError) as exc:
            # falling back to the numpy path is fine for correctness but
            # is a silent multi-x batch-assembly slowdown — say why
            detail = getattr(exc, "stderr", None)
            if detail:
                detail = detail.decode(errors="replace").strip()[:200]
            logging.getLogger(__name__).warning(
                "native batch assembler unavailable (%s); using the "
                "numpy fallback%s",
                exc,
                f" — compiler said: {detail}" if detail else "",
            )
            return None
        f64p = np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS")
        i64p = np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS")
        u8p = np.ctypeslib.ndpointer(np.uint8, flags="C_CONTIGUOUS")
        lib.gather_rows_f32.argtypes = [
            f64p, ctypes.c_int64, i64p, ctypes.c_int64, f64p,
        ]
        lib.gather_rows_u8_normalize.argtypes = [
            u8p, ctypes.c_int64, i64p, ctypes.c_int64,
            ctypes.c_float, ctypes.c_float, f64p,
        ]
        lib.normalize_rows_f32.argtypes = [
            f64p, ctypes.c_int64, ctypes.c_int64, f64p, f64p,
        ]
        lib.crop_gather_u8.argtypes = [
            u8p, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
            i64p, i64p, i64p, u8p, ctypes.c_int64,
            ctypes.c_int64, ctypes.c_int64, u8p,
        ]
        lib.crop_flip_is_wide.argtypes = [ctypes.c_int64]
        lib.crop_flip_is_wide.restype = ctypes.c_int32
        lib.gather_rows_u8_raw.argtypes = [
            u8p, ctypes.c_int64, i64p, ctypes.c_int64, u8p,
        ]
        _lib = lib
        return _lib


def available() -> bool:
    return _build_and_load() is not None


def _check_indices(indices: np.ndarray, n: int) -> np.ndarray:
    """The C side does raw pointer math: reject what numpy would reject
    (and the negatives numpy would wrap) BEFORE crossing the ABI."""
    idx = np.ascontiguousarray(indices, np.int64)
    if idx.size and (idx.min() < 0 or idx.max() >= n):
        raise IndexError(
            f"indices out of range [0, {n}): min={idx.min()} max={idx.max()}"
        )
    return idx


def gather_rows(data: np.ndarray, indices: np.ndarray) -> np.ndarray:
    """out[i] = data[indices[i]] — native parallel gather with numpy
    fallback.  ``data``: [n, ...] float32 C-contiguous."""
    lib = _build_and_load()
    flat = data.reshape(len(data), -1)
    idx = _check_indices(indices, len(data))  # both paths: no numpy wrap
    if (
        lib is None
        or flat.dtype != np.float32
        or not flat.flags["C_CONTIGUOUS"]
    ):
        return data[idx]
    out = np.empty((len(idx), flat.shape[1]), np.float32)
    lib.gather_rows_f32(flat, flat.shape[1], idx, len(idx), out)
    return out.reshape((len(idx),) + data.shape[1:])


def gather_rows_u8_raw(data: np.ndarray, indices: np.ndarray) -> np.ndarray:
    """Plain u8 row gather (no conversion) — feeds the u8->device path
    where the affine normalize runs on-device inside the XLA step."""
    lib = _build_and_load()
    flat = data.reshape(len(data), -1)
    idx = _check_indices(indices, len(data))
    if (
        lib is None
        or flat.dtype != np.uint8
        or not flat.flags["C_CONTIGUOUS"]
    ):
        return data[idx]
    out = np.empty((len(idx), flat.shape[1]), np.uint8)
    lib.gather_rows_u8_raw(flat, flat.shape[1], idx, len(idx), out)
    return out.reshape((len(idx),) + data.shape[1:])


def _crops_natively(data: np.ndarray) -> bool:
    return (
        _build_and_load() is not None
        and data.dtype == np.uint8
        and bool(data.flags["C_CONTIGUOUS"])
    )


def crop_paths(data: np.ndarray) -> tuple:
    """``(unflipped, flipped)``: the path :func:`crop_gather_u8` takes for
    an image of ``data`` by its flip bit, as the labels of
    ``znicz_loader_crop_images_total{path}``.  ``("numpy", "numpy")``
    where the library is not used at all; else ``"copy"`` (one ``memcpy``
    a row) and, for a flipped image, ``"flip_wide"`` (3-byte pixels on a
    CPU with SSSE3: sixteen bytes a turn) or ``"flip_pixel"``."""
    if not _crops_natively(data):
        return "numpy", "numpy"
    wide = _build_and_load().crop_flip_is_wide(data.shape[-1])
    return "copy", "flip_wide" if wide else "flip_pixel"


def crop_gather_u8(
    data: np.ndarray,
    indices: np.ndarray,
    oy: np.ndarray,
    ox: np.ndarray,
    flip: np.ndarray,
    out_h: int,
    out_w: int,
    out: Optional[np.ndarray] = None,
) -> np.ndarray:
    """Fused gather + crop + optional horizontal flip from packed u8 images.

    ``data``: [N, H, W, C] u8; per sample i the window at (oy[i], ox[i]) of
    size (out_h, out_w) is copied (W-reversed when flip[i]).  Output stays u8;
    normalization happens on-device.  Numpy fallback when the native library
    is unavailable or ``data`` is non-contiguous/mmap-backed-but-fancy.
    An unflipped row is one ``memcpy``; a flipped row of 3-byte pixels is
    reversed five pixels (a 16-byte load, one byte shuffle, a 16-byte
    store) a turn where the CPU has SSSE3, so a flipped crop costs what
    an unflipped one does (:func:`crop_paths` says which path runs here).
    ``out`` (optional): a C-contiguous [B, out_h, out_w, C] array of
    ``data``'s dtype to write into instead of a fresh one.
    """
    n, h, w, c = data.shape
    idx = _check_indices(indices, n)
    oy = np.ascontiguousarray(oy, np.int64)
    ox = np.ascontiguousarray(ox, np.int64)
    if oy.min(initial=0) < 0 or ox.min(initial=0) < 0 or (
        idx.size
        and (oy.max(initial=0) > h - out_h or ox.max(initial=0) > w - out_w)
    ):
        raise IndexError("crop window out of image bounds")
    flip_u8 = np.ascontiguousarray(flip, np.uint8)
    shape = (len(idx), out_h, out_w, c)
    if out is None:
        out = np.empty(shape, data.dtype)
    elif (
        out.shape != shape
        or out.dtype != data.dtype
        or not out.flags["C_CONTIGUOUS"]
        or not out.flags["WRITEABLE"]
    ):
        raise ValueError(
            f"out must be a writable C-contiguous {data.dtype} array of "
            f"shape {shape}"
        )
    # np.memmap works here too: the C side reads through page faults, which
    # is exactly how a larger-than-RAM packed dataset streams from disk
    if _crops_natively(data):
        _build_and_load().crop_gather_u8(
            data.reshape(-1), h, w, c, idx, oy, ox, flip_u8, len(idx),
            out_h, out_w, out.reshape(-1),
        )
        return out
    for i, j in enumerate(idx):
        win = data[j, oy[i] : oy[i] + out_h, ox[i] : ox[i] + out_w]
        out[i] = win[:, ::-1] if flip_u8[i] else win
    return out


def gather_rows_u8(
    data: np.ndarray,
    indices: np.ndarray,
    *,
    scale: float = 255.0,
    shift: float = 0.0,
) -> np.ndarray:
    """Gather + u8->f32 affine normalize in one native pass."""
    lib = _build_and_load()
    flat = data.reshape(len(data), -1)
    idx = _check_indices(indices, len(data))  # both paths: no numpy wrap
    if (
        lib is None
        or flat.dtype != np.uint8
        or not flat.flags["C_CONTIGUOUS"]
    ):
        return (
            data[idx].astype(np.float32) / scale + shift
        )
    out = np.empty((len(idx), flat.shape[1]), np.float32)
    lib.gather_rows_u8_normalize(
        flat, flat.shape[1], idx, len(idx), scale, shift, out
    )
    return out.reshape((len(idx),) + data.shape[1:])
