"""Filesystem-paired image/mask datasets with reference preprocess parity.

Behavior parity with the reference `BasicDataset`/`CarvanaDataset`
(reference utils/dataloading.py:12-78), re-expressed for a JAX/TPU host
pipeline:

  * sample IDs are filename stems of the images dir, dotfiles skipped
    (dataloading.py:19);
  * each item glob-pairs ``<id><mask_suffix>.*`` in the masks dir and
    ``<id>.*`` in the images dir, asserting exactly one match of each
    (dataloading.py:56-60);
  * loading supports PIL images plus ``.npy``/``.npz`` and ``.pt``/``.pth``
    tensors (dataloading.py:44-52);
  * images resize with BICUBIC, masks with NEAREST (dataloading.py:31);
  * images are scaled by /255, masks are left as raw integer labels
    (dataloading.py:39-40);
  * `CarvanaDataset` is `BasicDataset` with ``mask_suffix='_mask'``
    (dataloading.py:76-78).

TPU-first divergence (deliberate): items are **NHWC numpy** arrays — image
``(H, W, 3) float32``, mask ``(H, W) int32`` — not CHW torch tensors, because
XLA:TPU wants channels-last (SURVEY.md §7 hard-part 4). `newsize` keeps the
reference's ``(W, H)`` ordering (dataloading.py:29 reads it as ``newW, newH``).
"""

from __future__ import annotations

import logging
import os
import threading
from os.path import splitext
from pathlib import Path
from typing import Dict, Hashable, Optional, Sequence, Tuple

import numpy as np
from PIL import Image

from distributedpytorch_tpu.data import native

logger = logging.getLogger(__name__)

Item = Dict[str, np.ndarray]
#: Cache keys: the train loaders key by dataset index (int); the serving
#: tier keys by ``(path, size)`` tuples. Anything hashable works — the
#: cache itself never interprets the key.
Key = Hashable


class SampleCache:
    """Epoch-persistent, memory-budgeted cache of decoded samples.

    The epoch loop re-reads the SAME samples every epoch, yet the seed
    pipeline re-ran PIL/libjpeg decode + resize for each of them, every
    epoch — on a 1-core host that decode bound the whole run
    (docs/PERFORMANCE.md input-pipeline table). This cache sits under
    DataLoader's batch assembly: the first epoch decodes and stores items until the byte
    budget is full, later epochs serve hits straight from host memory.

    Deliberately no eviction: the access pattern is a uniform re-scan of
    the whole epoch (reshuffled order, same set), where any
    evict-on-full policy would thrash — every sample displaced is one
    that will be needed again next epoch. Whatever fits stays for the
    run; the remainder decodes each epoch, so a too-small budget
    degrades smoothly toward the uncached behavior.

    Sharded multi-process runs reshuffle BEFORE striding, so a rank's
    per-epoch sample set changes: epoch 2 is not a pure re-scan and its
    hit rate starts at ~|shard ∩ cached| rather than ~100%. Because
    nothing is evicted, each rank's cache still grows monotonically
    toward the full (budget-bounded) dataset and the hit rate converges
    over a few epochs — warm-up is slower, the steady state is the same.
    Size the per-process budget accordingly (it is per rank, not global).

    Thread-safe: the loader's decode pool and the placement worker hit
    it concurrently. Stored arrays are shared across epochs — callers
    must treat items as read-only (batch assembly np.stack-copies, so
    nothing downstream mutates them).

    The serving tier (serve/engine.py) reuses this as its request-path
    decode cache, keyed by ``(path, size)`` instead of dataset index:
    repeat traffic over the same objects skips PIL/libjpeg entirely.
    """

    def __init__(self, budget_bytes: int):
        self.budget_bytes = int(budget_bytes)
        self._items: Dict[Key, Item] = {}
        self._lock = threading.Lock()
        self.used_bytes = 0
        self.hits = 0
        self.misses = 0
        self._full_logged = False

    @staticmethod
    def _nbytes(item: Item) -> int:
        return sum(int(np.asarray(v).nbytes) for v in item.values())

    def get(self, idx: Key) -> Optional[Item]:
        with self._lock:
            item = self._items.get(idx)
            if item is None:
                self.misses += 1
            else:
                self.hits += 1
            return item

    def put(self, idx: Key, item: Item) -> bool:
        """Store if the budget allows; returns whether it was stored."""
        size = self._nbytes(item)
        with self._lock:
            if idx in self._items:
                return True
            if self.used_bytes + size > self.budget_bytes:
                if not self._full_logged:
                    self._full_logged = True
                    logger.info(
                        "sample cache full at %d items / %.1f MiB (budget "
                        "%.1f MiB) — remaining samples decode every epoch",
                        len(self._items),
                        self.used_bytes / 2**20,
                        self.budget_bytes / 2**20,
                    )
                return False
            # decouple from any whole-batch parent buffer: a row view
            # would pin the full decoded batch even when only this row
            # fits (np.array(copy=True), NOT ascontiguousarray — a
            # first-axis slice is already contiguous and would be
            # returned uncopied, silently retaining the parent)
            self._items[idx] = {
                k: np.array(v, copy=True) for k, v in item.items()
            }
            self.used_bytes += size
            return True

    def __len__(self) -> int:
        with self._lock:
            return len(self._items)


class BasicDataset:
    """Images dir + masks dir paired by filename stem."""

    def __init__(
        self,
        images_dir: str,
        masks_dir: str,
        newsize: Sequence[int] = (960, 640),
        mask_suffix: str = "",
    ):
        self.images_dir = Path(images_dir)
        self.masks_dir = Path(masks_dir)
        self.newsize = tuple(int(v) for v in newsize)
        self.mask_suffix = mask_suffix

        self.ids = [
            splitext(f)[0]
            for f in os.listdir(images_dir)
            if not f.startswith(".")
        ]
        if not self.ids:
            raise RuntimeError(
                f"No input file found in {images_dir}, make sure you put your images there"
            )
        self.ids.sort()  # listdir order is fs-dependent; sort for determinism
        logger.info("Creating dataset with %d examples", len(self.ids))

    def __len__(self) -> int:
        return len(self.ids)

    @classmethod
    def load(cls, filename) -> Image.Image:
        """PIL / .npy / .pt loading (reference dataloading.py:44-52)."""
        ext = splitext(str(filename))[1]
        if ext in (".npz", ".npy"):
            return Image.fromarray(np.load(filename))
        if ext in (".pt", ".pth"):
            import torch  # local import: torch is only needed for .pt masks

            return Image.fromarray(torch.load(filename).numpy())
        return Image.open(filename)

    @classmethod
    def preprocess(
        cls, pil_img: Image.Image, newsize: Sequence[int], is_mask: bool
    ) -> np.ndarray:
        """Resize + normalize (reference dataloading.py:27-42), NHWC output."""
        new_w, new_h = int(newsize[0]), int(newsize[1])
        assert new_w > 0 and new_h > 0, (
            "Scale is too small, resized images would have no pixel"
        )
        pil_img = pil_img.resize(
            (new_w, new_h), resample=Image.NEAREST if is_mask else Image.BICUBIC
        )
        arr = np.asarray(pil_img)

        if is_mask:
            return arr.astype(np.int32)

        if arr.ndim == 2:  # grayscale image → single channel, channels-last
            arr = arr[..., np.newaxis]
        return (arr / 255.0).astype(np.float32)

    def resolve_paths(self, idx: int) -> Tuple[str, str]:
        """(image_path, mask_path) for one sample, with the reference's
        exactly-one-glob-match asserts (dataloading.py:56-60)."""
        name = self.ids[idx]
        mask_files = list(self.masks_dir.glob(name + self.mask_suffix + ".*"))
        img_files = list(self.images_dir.glob(name + ".*"))
        assert len(mask_files) == 1, (
            f"Either no mask or multiple masks found for the ID {name}: {mask_files}"
        )
        assert len(img_files) == 1, (
            f"Either no image or multiple images found for the ID {name}: {img_files}"
        )
        return str(img_files[0]), str(mask_files[0])

    use_native = True  # class-level toggle: C++ decode path when available

    def __getitem__(self, idx: int) -> Item:
        img_path, mask_path = self.resolve_paths(idx)

        if (
            self.use_native
            and native.supports(img_path)
            and native.supports(mask_path)
        ):
            if native.get_lib() is not None:
                image, mask = native.load_item(
                    img_path, mask_path, self.newsize[0], self.newsize[1]
                )
                return {"image": image, "mask": mask}

        mask = self.load(mask_path)
        img = self.load(img_path)
        assert img.size == mask.size, (
            f"Image and mask should be the same size, "
            f"but are {img.size} and {mask.size}"
        )
        return {
            "image": self.preprocess(img, self.newsize, is_mask=False),
            "mask": self.preprocess(mask, self.newsize, is_mask=True),
        }


class CarvanaDataset(BasicDataset):
    """Carvana naming convention: masks end in ``_mask``
    (reference dataloading.py:76-78)."""

    def __init__(self, images_dir, masks_dir, newsize: Sequence[int] = (960, 640)):
        super().__init__(images_dir, masks_dir, newsize, mask_suffix="_mask")


def build_dataset(
    images_dir: str, masks_dir: str, newsize: Sequence[int] = (960, 640)
) -> BasicDataset:
    """Carvana-first with BasicDataset fallback — the reference's try/except
    chain (reference utils/train_utils.py:27-32). Unlike the reference, the
    Carvana attempt probes one item: mask pairing only fails at glob time, so
    a constructor-only try would defer the failure to mid-training."""
    try:
        ds = CarvanaDataset(images_dir, masks_dir, newsize)
        ds[0]
        logger.info("Carvana dataset detected")
        return ds
    except (AssertionError, RuntimeError):
        logger.info("Falling back to basic dataset")
        return BasicDataset(images_dir, masks_dir, newsize)


class SyntheticSegmentationDataset:
    """In-memory procedural car-ish blobs — same item contract as
    `BasicDataset`, no disk or PIL in the loop.

    Serves two roles the reference has no answer for (SURVEY.md §4):
    deterministic unit-test data, and a benchmark input source that removes
    disk/JPEG decode from measured step time.
    """

    def __init__(
        self,
        length: int = 64,
        newsize: Sequence[int] = (960, 640),
        seed: int = 0,
    ):
        self.length = length
        self.newsize = tuple(int(v) for v in newsize)
        self.seed = seed
        self.ids = [f"synthetic_{i:04d}" for i in range(length)]

    def __len__(self) -> int:
        return self.length

    def __getitem__(self, idx: int) -> Item:
        if not 0 <= idx < self.length:
            raise IndexError(idx)
        w, h = self.newsize
        rng = np.random.default_rng(self.seed * 1_000_003 + idx)
        image = rng.random((h, w, 3), dtype=np.float32)
        # an axis-aligned ellipse "car" per sample
        cy, cx = rng.integers(h // 4, 3 * h // 4), rng.integers(w // 4, 3 * w // 4)
        ry, rx = rng.integers(h // 8, h // 4), rng.integers(w // 8, w // 4)
        yy, xx = np.ogrid[:h, :w]
        mask = (
            ((yy - cy) / max(ry, 1)) ** 2 + ((xx - cx) / max(rx, 1)) ** 2 <= 1.0
        ).astype(np.int32)
        image[..., 0] = np.where(mask, 0.25 + 0.5 * image[..., 0], image[..., 0])
        return {"image": image, "mask": mask}


def write_synthetic_carvana_tree(
    root: str,
    n: int = 8,
    size_wh: Tuple[int, int] = (96, 64),
    seed: int = 0,
) -> Tuple[str, str]:
    """Materialize a tiny Carvana-layout tree (train_hq/ + train_masks/ with
    ``_mask.gif`` masks) for filesystem-path tests. Returns (images, masks)."""
    images_dir = os.path.join(root, "train_hq")
    masks_dir = os.path.join(root, "train_masks")
    os.makedirs(images_dir, exist_ok=True)
    os.makedirs(masks_dir, exist_ok=True)
    src = SyntheticSegmentationDataset(length=n, newsize=size_wh, seed=seed)
    for i in range(n):
        item = src[i]
        name = f"car_{i:03d}"
        img8 = (item["image"] * 255).astype(np.uint8)
        Image.fromarray(img8).save(os.path.join(images_dir, name + ".jpg"))
        # Carvana masks are {0,1} GIFs — the ``== 1`` binarization in the loss
        # depends on this (SURVEY.md §2 quirk 3).
        Image.fromarray(item["mask"].astype(np.uint8)).save(
            os.path.join(masks_dir, name + "_mask.gif")
        )
    return images_dir, masks_dir
