"""The one generator of training traffic: a data set made from the seed.

A traffic mix is a file of parameters under ``benchmark/traffic/``; this
module reads it. ``kind: synthetic_blobs`` is an in-memory data set with
the item contract of the program's loaders (``image`` (H, W, 3) float32
in [0, 1), ``mask`` (H, W) int32 in {0, 1}): uniform noise with one
axis-aligned ellipse whose red channel is shifted, every item drawn from
``seed`` and its index, so that every row of an epoch differs. Copied
from the program's ``data/dataset.SyntheticSegmentationDataset`` so that
a change there cannot move the yardstick.
"""

from __future__ import annotations

import json
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
#: Values at the start of an image that tell the items of a mix apart.
HEAD = 16


def load(name: str) -> dict:
    with open(os.path.join(HERE, "traffic", name + ".json")) as f:
        return json.load(f)


class SyntheticBlobs:
    def __init__(self, samples: int, image_size, seed: int):
        self.length = int(samples)
        self.newsize = tuple(int(v) for v in image_size)  # (W, H)
        self.seed = int(seed)
        self.ids = [f"synthetic_{i:04d}" for i in range(self.length)]

    def __len__(self) -> int:
        return self.length

    def head(self, idx: int) -> bytes:
        """How item ``idx``'s image begins, without making the item: the
        generator's first draws (row 0 lies outside every ellipse, whose
        centre is at least a quarter of the height down and whose radius
        is less)."""
        rng = np.random.default_rng(self.seed * 1_000_003 + idx)
        return rng.random(HEAD, dtype=np.float32).tobytes()

    def __getitem__(self, idx: int) -> dict:
        if not 0 <= idx < self.length:
            raise IndexError(idx)
        w, h = self.newsize
        rng = np.random.default_rng(self.seed * 1_000_003 + idx)
        image = rng.random((h, w, 3), dtype=np.float32)
        cy, cx = rng.integers(h // 4, 3 * h // 4), rng.integers(w // 4, 3 * w // 4)
        ry, rx = rng.integers(h // 8, h // 4), rng.integers(w // 8, w // 4)
        yy, xx = np.ogrid[:h, :w]
        mask = (
            ((yy - cy) / max(ry, 1)) ** 2 + ((xx - cx) / max(rx, 1)) ** 2 <= 1.0
        ).astype(np.int32)
        image[..., 0] = np.where(mask, 0.25 + 0.5 * image[..., 0], image[..., 0])
        return {"image": image, "mask": mask}


def build(params: dict, image_size, seed: int):
    if params["kind"] != "synthetic_blobs":
        raise ValueError(f"unknown traffic kind {params['kind']!r}")
    return SyntheticBlobs(params["samples"], image_size, seed)


def same_rows(data, batches):
    """``batches`` (what the program's loader stacked, ``image`` and
    ``mask``) made anew from ``data``, the mix's own rows: ``([(image,
    mask), ...], altered)``, batch by batch in the loader's order. A row
    of the loader's is found in the mix by how its image begins and then
    held against that item value for value; ``altered`` counts the rows
    that begin like no item or differ from theirs, and such a row is
    passed on as the loader gave it."""
    found = {data.head(i): i for i in range(len(data))}
    out, altered = [], 0
    for batch in batches:
        images, masks = [], []
        for image, mask in zip(batch["image"], batch["mask"]):
            idx = found.get(
                np.asarray(image, np.float32).ravel()[:HEAD].tobytes())
            item = {"image": image, "mask": mask} if idx is None else data[idx]
            if idx is None or not (np.array_equal(item["image"], image)
                                   and np.array_equal(item["mask"], mask)):
                altered += 1
            images.append(item["image"])
            masks.append(item["mask"])
        out.append((np.stack(images), np.stack(masks)))
    return out, altered
