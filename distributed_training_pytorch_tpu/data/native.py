"""ctypes bindings for the native data-loader runtime (``csrc/dtp_native.cpp``).

The reference's host-side image work runs in prebuilt native code (OpenCV,
``dataset/example_dataset.py:57-60``; albumentations SIMD) under torch
DataLoader workers. This module is the TPU build's native path: one GIL-free
C++ call per *batch* (decode+resize+normalize, CIFAR-style crop/flip/
normalize, or plain normalize), internally multithreaded, with Philox
randomness keyed identically to the Python pipeline
(``data/transforms.philox_key``) so results are deterministic across hosts.

The library is compiled on first use (``make -C csrc``) and cached next to
this file. When the build fails the pure-Python path is used and a warning
names the failed command — ``available()`` reports which path is active.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
import warnings
from typing import Sequence

import numpy as np

_LIB_NAME = "libdtp_native.so"
_LIB_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "_native")
_CSRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "csrc")

_lib = None
_lib_lock = threading.Lock()
_build_failed = False


def _load():
    """Load (building if necessary) the native library; None if unavailable."""
    global _lib, _build_failed
    if _lib is not None or _build_failed:
        return _lib
    with _lib_lock:
        if _lib is not None or _build_failed:
            return _lib
        path = os.path.join(_LIB_DIR, _LIB_NAME)
        # (Re)build when the library is missing OR stale — an existing .so
        # older than any csrc source must not silently shadow edited code.
        needs_build = not os.path.exists(path)
        if not needs_build and os.path.isdir(_CSRC):
            # Only files the make target actually depends on — including the
            # Makefile here would make an edited Makefile trigger a perpetual
            # no-op `make` (its target depends on the .cpp alone).
            src_mtime = max(
                (
                    os.path.getmtime(os.path.join(_CSRC, f))
                    for f in os.listdir(_CSRC)
                    if f.endswith((".cpp", ".cc", ".h", ".hpp"))
                ),
                default=0.0,
            )
            needs_build = src_mtime > os.path.getmtime(path)
        if needs_build and os.path.isdir(_CSRC):
            try:
                subprocess.run(
                    ["make", "-C", _CSRC],
                    check=True,
                    capture_output=True,
                    timeout=120,
                )
            except (subprocess.SubprocessError, OSError) as e:
                # Never silent: the Python path is correct but slower, and a
                # run that wanted the native one must be able to see why it
                # did not get it (chip_smoke.py treats this as a failure).
                detail = (getattr(e, "stderr", b"") or b"").decode(errors="replace")
                reason = (detail.strip().splitlines() or [repr(e)])[-1]
                if not os.path.exists(path):
                    _build_failed = True
                    warnings.warn(
                        f"building {_LIB_NAME} failed (`make -C {_CSRC}`: "
                        f"{reason}); using the pure-Python input path"
                    )
                    return None
                warnings.warn(
                    f"{_LIB_NAME} is older than csrc sources and rebuilding "
                    f"failed ({reason}); using the stale library"
                )
        if not os.path.exists(path):
            _build_failed = True
            return None
        try:
            lib = ctypes.CDLL(path)
        except OSError:
            _build_failed = True
            return None
        i64, i32, u64 = ctypes.c_int64, ctypes.c_int, ctypes.c_uint64
        fptr = np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS")
        u8ptr = np.ctypeslib.ndpointer(np.uint8, flags="C_CONTIGUOUS")
        i64ptr = np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS")
        lib.dtp_decode_resize_normalize.restype = i64
        lib.dtp_decode_resize_normalize.argtypes = [
            ctypes.POINTER(ctypes.c_char_p), i64, i32, i32, fptr, fptr, fptr, i32,
        ]
        lib.dtp_augment_crop_flip.restype = i64
        lib.dtp_augment_crop_flip.argtypes = [
            u8ptr, i64, i32, i32, i32, u64, u64, i64ptr, fptr, fptr, i32, fptr, i32,
        ]
        lib.dtp_normalize.restype = i64
        lib.dtp_normalize.argtypes = [u8ptr, i64, i32, i32, fptr, fptr, fptr, i32]
        lib.dtp_augment_crop_flip_u8.restype = i64
        lib.dtp_augment_crop_flip_u8.argtypes = [
            u8ptr, i64, i32, i32, i32, u64, u64, i64ptr, i32, u8ptr, i32,
        ]
        lib.dtp_decode_resize_normalize_bytes.restype = i64
        lib.dtp_decode_resize_normalize_bytes.argtypes = [
            ctypes.POINTER(ctypes.c_char_p), i64ptr, i64, i32, i32, fptr, fptr, fptr, i32,
        ]
        lib.dtp_decode_resize_u8_bytes.restype = i64
        lib.dtp_decode_resize_u8_bytes.argtypes = [
            ctypes.POINTER(ctypes.c_char_p), i64ptr, i64, i32, i32, u8ptr, i32,
        ]
        f32 = ctypes.c_float
        lib.dtp_decode_rrc_flip_u8_bytes.restype = i64
        lib.dtp_decode_rrc_flip_u8_bytes.argtypes = [
            ctypes.POINTER(ctypes.c_char_p), i64ptr, i64, i32, i32, u64, u64,
            i64ptr, i32, f32, f32, f32, f32, u8ptr, i32,
        ]
        _lib = lib
        return _lib


def available() -> bool:
    return _load() is not None


class DecodeError(ValueError):
    """A payload in a native decode batch failed; ``index`` is the position
    within the sequence passed to that call (callers slicing a larger batch
    remap it — see :func:`mixed_native_batch`)."""

    def __init__(self, index: int, what: str = "record payload"):
        self.index = index
        super().__init__(f"failed to decode {what} #{index}")


def _threads(n: int | None) -> int:
    return n if n is not None else min(16, os.cpu_count() or 1)


def decode_resize_normalize(
    paths: Sequence[str],
    height: int,
    width: int,
    mean: np.ndarray,
    std: np.ndarray,
    *,
    threads: int | None = None,
) -> np.ndarray:
    """Decode JPEG/PNG files -> [N, H, W, 3] float32, resized (cv2-compatible
    bilinear) and normalized, in one native call."""
    lib = _load()
    if lib is None:
        raise RuntimeError("native library unavailable")
    n = len(paths)
    out = np.empty((n, height, width, 3), np.float32)
    arr = (ctypes.c_char_p * n)(*[os.fsencode(p) for p in paths])
    rc = lib.dtp_decode_resize_normalize(
        arr, n, height, width,
        np.ascontiguousarray(mean, np.float32),
        np.ascontiguousarray(std, np.float32),
        out, _threads(threads),
    )
    if rc:
        raise ValueError(f"failed to decode {paths[rc - 1]!r}")
    return out


def decode_resize_normalize_bytes(
    payloads: Sequence[bytes],
    height: int,
    width: int,
    mean: np.ndarray,
    std: np.ndarray,
    *,
    threads: int | None = None,
) -> np.ndarray:
    """In-memory JPEG/PNG payloads (record-file shards) -> [N, H, W, 3]
    float32, resized + normalized in one native call (no temp files)."""
    lib = _load()
    if lib is None:
        raise RuntimeError("native library unavailable")
    n = len(payloads)
    lengths = np.asarray([len(p) for p in payloads], np.int64)
    # Zero-copy: c_char_p elements point straight at each bytes object's
    # buffer (lengths are passed explicitly; embedded NULs are fine).
    bufs = (ctypes.c_char_p * n)(*payloads)
    out = np.empty((n, height, width, 3), np.float32)
    rc = lib.dtp_decode_resize_normalize_bytes(
        bufs, lengths, n, height, width,
        np.ascontiguousarray(mean, np.float32),
        np.ascontiguousarray(std, np.float32),
        out, _threads(threads),
    )
    if rc:
        raise DecodeError(rc - 1)
    return out


def decode_resize_u8_bytes(
    payloads: Sequence[bytes],
    height: int,
    width: int,
    *,
    threads: int | None = None,
) -> np.ndarray:
    """In-memory JPEG/PNG payloads -> [N, H, W, 3] uint8 (decode + resize, no
    normalize) — the ship-uint8 train path; pair with
    :func:`augment_crop_flip_u8` and on-device ``models.InputNormalizer``."""
    lib = _load()
    if lib is None:
        raise RuntimeError("native library unavailable")
    n = len(payloads)
    lengths = np.asarray([len(p) for p in payloads], np.int64)
    bufs = (ctypes.c_char_p * n)(*payloads)
    out = np.empty((n, height, width, 3), np.uint8)
    rc = lib.dtp_decode_resize_u8_bytes(bufs, lengths, n, height, width, out, _threads(threads))
    if rc:
        raise DecodeError(rc - 1)
    return out


def decode_rrc_flip_u8_bytes(
    payloads: Sequence[bytes],
    height: int,
    width: int,
    indices: np.ndarray,
    *,
    seed: int,
    epoch: int,
    hflip: bool = True,
    scale: tuple[float, float] = (0.08, 1.0),
    ratio: tuple[float, float] = (3 / 4, 4 / 3),
    threads: int | None = None,
) -> np.ndarray:
    """In-memory JPEG/PNG payloads -> [N, H, W, 3] uint8 via decode +
    RANDOM-RESIZED-CROP + optional hflip fused in one native call — the
    ImageNet train augmentation (10-attempt sampling with the repo's
    transforms.random_resized_crop center-square fallback; torchvision's
    fallback ratio-clamps instead), Philox-keyed per (seed, epoch,
    indices[i]). The
    full-size decode never crosses back into Python."""
    lib = _load()
    if lib is None:
        raise RuntimeError("native library unavailable")
    n = len(payloads)
    lengths = np.asarray([len(p) for p in payloads], np.int64)
    bufs = (ctypes.c_char_p * n)(*payloads)
    out = np.empty((n, height, width, 3), np.uint8)
    rc = lib.dtp_decode_rrc_flip_u8_bytes(
        bufs, lengths, n, height, width, seed, epoch,
        np.ascontiguousarray(indices, np.int64), int(hflip),
        float(scale[0]), float(scale[1]), float(ratio[0]), float(ratio[1]),
        out, _threads(threads),
    )
    if rc:
        raise DecodeError(rc - 1)
    return out


def mixed_native_batch(
    n, height, width, native_positions, native_fn, py_fn, *, dtype=np.float32
) -> np.ndarray:
    """Assemble a decoded batch where some rows take the native batch call and
    the rest fall back per record (shared by the folder and record sources).

    ``native_positions``: batch positions decodable natively (position-based —
    row indices can repeat under pad_final). ``native_fn(positions)`` returns
    the stacked native results for those positions; ``py_fn(position)`` one
    fallback row.
    """
    images = np.empty((n, height, width, 3), dtype)
    if native_positions:
        try:
            images[native_positions] = native_fn(native_positions)
        except DecodeError as e:
            # remap the subset-relative index to the batch position, so the
            # error names the record an operator would actually look for
            raise DecodeError(native_positions[e.index], "batch record") from None
    for p in set(range(n)) - set(native_positions):
        images[p] = py_fn(p)
    return images


def augment_crop_flip(
    images: np.ndarray,
    indices: np.ndarray,
    *,
    pad: int,
    seed: int,
    epoch: int,
    mean: np.ndarray,
    std: np.ndarray,
    hflip: bool = True,
    threads: int | None = None,
) -> np.ndarray:
    """Deterministic reflect-pad/random-crop/hflip/normalize over a uint8
    NHWC batch. Randomness keyed per record by (seed, epoch, indices[i])."""
    lib = _load()
    if lib is None:
        raise RuntimeError("native library unavailable")
    images = np.ascontiguousarray(images, np.uint8)
    n, h, w, c = images.shape
    assert c == 3
    out = np.empty((n, h, w, 3), np.float32)
    lib.dtp_augment_crop_flip(
        images, n, h, w, pad, seed, epoch,
        np.ascontiguousarray(indices, np.int64),
        np.ascontiguousarray(mean, np.float32),
        np.ascontiguousarray(std, np.float32),
        int(hflip), out, _threads(threads),
    )
    return out


def augment_crop_flip_u8(
    images: np.ndarray,
    indices: np.ndarray,
    *,
    pad: int,
    seed: int,
    epoch: int,
    hflip: bool = True,
    threads: int | None = None,
) -> np.ndarray:
    """Crop/flip only, uint8 -> uint8 (same Philox stream as
    :func:`augment_crop_flip`). For device-side normalization: ship 1 byte
    per pixel over the host->device link instead of 4."""
    lib = _load()
    if lib is None:
        raise RuntimeError("native library unavailable")
    images = np.ascontiguousarray(images, np.uint8)
    n, h, w, c = images.shape
    assert c == 3
    out = np.empty((n, h, w, 3), np.uint8)
    lib.dtp_augment_crop_flip_u8(
        images, n, h, w, pad, seed, epoch,
        np.ascontiguousarray(indices, np.int64),
        int(hflip), out, _threads(threads),
    )
    return out


class NativeCropFlipU8:
    """Batch transform that keeps images uint8 (crop/flip only); pair with
    on-device normalization (``models.InputNormalizer``) so the H2D link
    carries 4x fewer bytes and XLA fuses the normalize into the first conv."""

    def __init__(self, *, pad: int = 4, seed: int = 0, train: bool = True):
        self.pad = pad
        self.seed = seed
        self.train = train

    def batch_apply(self, images: np.ndarray, indices: np.ndarray, epoch: int) -> np.ndarray:
        if not self.train:
            return np.ascontiguousarray(images, np.uint8)
        return augment_crop_flip_u8(
            images, np.asarray(indices, np.int64),
            pad=self.pad, seed=self.seed, epoch=epoch,
        )

    def __call__(self, img: np.ndarray, *, epoch: int = 0, index: int = 0) -> np.ndarray:
        return self.batch_apply(img[None], np.array([index]), epoch)[0]


class NativeCropFlipNormalize:
    """Batch transform (loader ``batch_apply`` protocol): reflect-pad-``pad``
    random crop + horizontal flip + normalize over uint8 NHWC batches, one
    native call per batch. ``train=False`` skips the random ops (val path).

    Randomness is keyed by (seed, epoch, record index) like the Python
    pipeline; the two paths draw differently from Philox, so each is
    deterministic and host-consistent but they are not bit-identical to each
    other."""

    def __init__(self, mean, std, *, pad: int = 4, seed: int = 0, train: bool = True):
        self.mean = np.ascontiguousarray(mean, np.float32)
        self.std = np.ascontiguousarray(std, np.float32)
        self.pad = pad
        self.seed = seed
        self.train = train

    def batch_apply(self, images: np.ndarray, indices: np.ndarray, epoch: int) -> np.ndarray:
        if not self.train:
            return normalize(images, self.mean, self.std)
        return augment_crop_flip(
            images,
            np.asarray(indices, np.int64),
            pad=self.pad,
            seed=self.seed,
            epoch=epoch,
            mean=self.mean,
            std=self.std,
        )

    def __call__(self, img: np.ndarray, *, epoch: int = 0, index: int = 0) -> np.ndarray:
        """Single-record fallback (loader Python path)."""
        return self.batch_apply(img[None], np.array([index]), epoch)[0]


def normalize(
    images: np.ndarray,
    mean: np.ndarray,
    std: np.ndarray,
    *,
    threads: int | None = None,
) -> np.ndarray:
    """uint8 NHWC -> normalized float32, one native call."""
    lib = _load()
    if lib is None:
        raise RuntimeError("native library unavailable")
    images = np.ascontiguousarray(images, np.uint8)
    n, h, w, c = images.shape
    assert c == 3
    out = np.empty((n, h, w, 3), np.float32)
    lib.dtp_normalize(
        images, n, h, w,
        np.ascontiguousarray(mean, np.float32),
        np.ascontiguousarray(std, np.float32),
        out, _threads(threads),
    )
    return out
