"""The seeded uint8 stand-in for packed ImageNet files, written once per
checkout in the layout ``ImageNetLoader`` memory-maps (the set-up of
``bench.py:_sec_imagenet``, without its clock).  The content is fixed: a
run's ``--seed`` drives the weights, the shuffle, the crops and the
flips, not the bytes on disk."""

from __future__ import annotations

import json
import os

import numpy as np
from numpy.lib.format import open_memmap

_TILE = 256  # distinct random images; every other image is a tile shifted


def ensure(directory: str, n_images: int, size: int, n_classes: int) -> str:
    """``directory/packed-<n>x<size>`` holding ``train_images.npy`` [n,
    size, size, 3] u8, ``train_labels.npy``, ``classes.json`` and
    ``mean_rgb.json``; made if it is not there.  Every image differs:
    image i is random tile ``i % 256`` plus ``i // 256`` (mod 256)."""
    out = os.path.join(directory, f"packed-{n_images}x{size}")
    done = os.path.join(out, "complete")
    if os.path.exists(done):
        return out
    os.makedirs(out, exist_ok=True)
    rng = np.random.default_rng(20120930)
    tile = rng.integers(0, 256, (_TILE, size, size, 3), dtype=np.uint8)
    images = open_memmap(
        os.path.join(out, "train_images.npy"), mode="w+", dtype=np.uint8,
        shape=(n_images, size, size, 3),
    )
    for lo in range(0, n_images, _TILE):
        hi = min(lo + _TILE, n_images)
        images[lo:hi] = tile[: hi - lo] + np.uint8((lo // _TILE) % 256)
    images.flush()
    del images
    labels = rng.integers(0, n_classes, (n_images,)).astype(np.int32)
    np.save(os.path.join(out, "train_labels.npy"), labels)
    with open(os.path.join(out, "classes.json"), "w") as f:
        json.dump([f"class{i:04d}" for i in range(n_classes)], f)
    with open(os.path.join(out, "mean_rgb.json"), "w") as f:
        json.dump([0.5, 0.5, 0.5], f)
    with open(done, "w") as f:
        f.write("ok\n")
    return out
