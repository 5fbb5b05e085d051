"""ctypes binding for the native data-loading runtime (native/dpt_data.cpp).

The reference decodes with PIL inside torch DataLoader worker processes
(reference utils/dataloading.py:44-52, utils/train_utils.py:40). Here the
hot path is one C call per *batch*: JPEG/PNG/GIF decode, Pillow-parity
BICUBIC/NEAREST resize, /255 normalize, and NHWC assembly all happen in
C++ threads (native/dpt_data.cpp dpt_load_batch) with no Python in the
per-image loop.

The library is built from the committed ``native/dpt_data.cpp`` +
``Makefile``: `get_lib()` runs ``make`` every time (a no-op when the
object is newer than its source), so whatever ``libdpt_data.so`` lies on
disk is never trusted over the source. Where a toolchain is present, a
failed build or load is an error. Only a machine with no ``make`` or no
C++ compiler uses the PIL path, and says so at WARNING;
:func:`decode_path` names the path that is live.
"""

from __future__ import annotations

import ctypes
import logging
import os
import shutil
import subprocess
import threading
from os.path import splitext
from typing import List, Optional, Sequence, Tuple

import numpy as np

logger = logging.getLogger(__name__)

# Formats dpt_data.cpp can decode (decode_file, native/dpt_data.cpp:264-287).
_SUPPORTED_EXTS = {".jpg", ".jpeg", ".png", ".gif"}

_NATIVE_DIR = os.path.join(os.path.dirname(__file__), os.pardir, "native")
_LIB_PATH = os.path.abspath(os.path.join(_NATIVE_DIR, "libdpt_data.so"))

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_lib_attempted = False


def supports(path: str) -> bool:
    """True if the native decoder handles this file's format."""
    return splitext(str(path))[1].lower() in _SUPPORTED_EXTS


def _toolchain() -> bool:
    """make and a C++ compiler are both on PATH (CXX honoured like the
    Makefile honours it)."""
    cxx = os.environ.get("CXX", "g++").split()[0]
    return bool(shutil.which("make") and shutil.which(cxx))


def build(force: bool = False) -> None:
    """Bring ``libdpt_data.so`` up to date with its committed source via
    the Makefile; ``force`` rebuilds unconditionally (``make -B`` — what
    the chip smoke does, so a stale object copied along with the tree is
    never what runs). Raises ``RuntimeError`` when the build fails."""
    cmd = ["make", "-C", os.path.abspath(_NATIVE_DIR), "libdpt_data.so"]
    if force:
        cmd.insert(1, "-B")
    proc = subprocess.run(cmd, capture_output=True, text=True, timeout=300)
    if proc.returncode != 0 or not os.path.exists(_LIB_PATH):
        raise RuntimeError(
            f"native data loader build failed (rc={proc.returncode}):\n"
            f"{proc.stderr[-2000:]}"
        )


def _bind(lib: ctypes.CDLL) -> ctypes.CDLL:
    lib.dpt_load_item.restype = ctypes.c_int
    lib.dpt_load_item.argtypes = [
        ctypes.c_char_p,  # img_path (nullable)
        ctypes.c_char_p,  # mask_path (nullable)
        ctypes.c_int,  # out_w
        ctypes.c_int,  # out_h
        ctypes.c_void_p,  # float* img_out
        ctypes.c_void_p,  # int32* mask_out
    ]
    lib.dpt_load_batch.restype = ctypes.c_int
    lib.dpt_load_batch.argtypes = [
        ctypes.POINTER(ctypes.c_char_p),  # img_paths (nullable)
        ctypes.POINTER(ctypes.c_char_p),  # mask_paths (nullable)
        ctypes.c_int,  # n
        ctypes.c_int,  # out_w
        ctypes.c_int,  # out_h
        ctypes.c_int,  # n_threads
        ctypes.c_void_p,  # float* imgs_out
        ctypes.c_void_p,  # int32* masks_out
    ]
    lib.dpt_version.restype = ctypes.c_char_p
    return lib


def get_lib() -> Optional[ctypes.CDLL]:
    """The native library, built from source first. None only where no
    toolchain exists (callers then use the PIL path); with a toolchain,
    a failed build or load raises."""
    global _lib, _lib_attempted
    if _lib is not None or _lib_attempted:
        return _lib
    with _lock:
        if _lib is not None or _lib_attempted:
            return _lib
        if not _toolchain():
            _lib_attempted = True
            logger.warning(
                "no make/C++ toolchain on PATH: native data loader not "
                "built; decoding with PIL"
            )
            return None
        build()
        _lib = _bind(ctypes.CDLL(_LIB_PATH))
        _lib_attempted = True
        logger.info("native data loader: %s", _lib.dpt_version().decode())
        return _lib


def decode_path() -> str:
    """Which decode path is live: ``"native"`` or ``"PIL"``."""
    return "native" if get_lib() is not None else "PIL"


def load_item(
    img_path: Optional[str],
    mask_path: Optional[str],
    out_w: int,
    out_h: int,
) -> Tuple[Optional[np.ndarray], Optional[np.ndarray]]:
    """Decode + preprocess one image/mask pair into NHWC numpy.

    Returns (image (H,W,3) float32 in [0,1] or None, mask (H,W) int32 or
    None) matching BasicDataset.preprocess (data/dataset.py:86-105).
    """
    lib = get_lib()
    if lib is None:
        raise RuntimeError("native library unavailable")
    img = (
        np.empty((out_h, out_w, 3), dtype=np.float32)
        if img_path is not None
        else None
    )
    mask = (
        np.empty((out_h, out_w), dtype=np.int32)
        if mask_path is not None
        else None
    )
    rc = lib.dpt_load_item(
        img_path.encode() if img_path is not None else None,
        mask_path.encode() if mask_path is not None else None,
        int(out_w),
        int(out_h),
        img.ctypes.data if img is not None else None,
        mask.ctypes.data if mask is not None else None,
    )
    if rc != 0:
        which = img_path if rc == 1 else mask_path
        raise RuntimeError(f"native decode failed (rc={rc}): {which}")
    return img, mask


def load_batch(
    img_paths: Optional[Sequence[str]],
    mask_paths: Optional[Sequence[str]],
    out_w: int,
    out_h: int,
    n_threads: int = 4,
) -> Tuple[Optional[np.ndarray], Optional[np.ndarray]]:
    """Assemble a whole batch in one C call (thread pool inside,
    native/dpt_data.cpp dpt_load_batch).

    Returns (images (N,H,W,3) float32, masks (N,H,W) int32); either is None
    when its path list is None.
    """
    lib = get_lib()
    if lib is None:
        raise RuntimeError("native library unavailable")
    n = len(img_paths) if img_paths is not None else len(mask_paths)
    if mask_paths is not None and img_paths is not None:
        assert len(img_paths) == len(mask_paths)

    def _c_paths(paths: Optional[Sequence[str]]):
        if paths is None:
            return None
        arr = (ctypes.c_char_p * n)(*[p.encode() for p in paths])
        return arr

    imgs = (
        np.empty((n, out_h, out_w, 3), dtype=np.float32)
        if img_paths is not None
        else None
    )
    masks = (
        np.empty((n, out_h, out_w), dtype=np.int32)
        if mask_paths is not None
        else None
    )
    rc = lib.dpt_load_batch(
        _c_paths(img_paths),
        _c_paths(mask_paths),
        n,
        int(out_w),
        int(out_h),
        int(n_threads),
        imgs.ctypes.data if imgs is not None else None,
        masks.ctypes.data if masks is not None else None,
    )
    if rc != 0:
        i = rc - 100
        which: List[str] = []
        if img_paths is not None:
            which.append(img_paths[i])
        if mask_paths is not None:
            which.append(mask_paths[i])
        raise RuntimeError(f"native decode failed (rc={rc}): {which}")
    return imgs, masks
