"""ImageNet-style training pipeline: pack, crop, flip, device-side normalize.

Capability parity with the reference ImageNet preprocessing pipeline
(``znicz/loader/`` + ``znicz/samples/ImageNet/`` preparation scripts
[SURVEY.md 2.3 "Znicz loaders", "Samples"]): resize to a canonical size,
train-time random crop + horizontal flip, mean subtraction, eval center
crop.  Re-founded TPU-first:

- **Pack once, stream forever.**  ``pack_image_dir`` converts a directory
  tree (``train/<class>/*.jpg``) into per-split ``.npy`` u8 arrays (short
  side resized, center-cropped to ``size``x``size``).  The loader memory-maps
  them, so datasets larger than host RAM stream from disk.
- **Crops are native.**  Per-minibatch random crop + flip runs in
  ``native/batch_assembler.cc`` (``crop_gather_u8``), not a Python loop:
  an unflipped row is a ``memcpy``, a flipped row is reversed sixteen
  bytes (five pixels) a turn, so either costs what a copy costs;
  ``znicz_loader_crop_images_total{path}`` says which path the images of
  a run took.
- **Normalization is on-device.**  Minibatches cross host->device as u8
  (4x fewer bytes than f32); the affine u8->f32 + channel-mean subtraction
  happens inside the jitted step (``device_preproc``), where XLA fuses it
  into the first convolution's input.
"""

from __future__ import annotations

import json
import os
import sys
import time
from typing import Dict, Optional, Tuple

import numpy as np

from znicz_tpu import observability
from znicz_tpu.core import prng
from znicz_tpu.loader.base import (
    SPLITS,
    TRAIN,
    Loader,
    Minibatch,
    pool_concat as base_pool_concat,
    pool_offsets as base_pool_offsets,
)
from znicz_tpu.loader.pool_sharded import PoolShardedMixin
from znicz_tpu.loader.image import IMAGE_EXTENSIONS, _read_image
from znicz_tpu.observability import pipeline as _pipeline

MEAN_FILE = "mean_rgb.json"
CLASSES_FILE = "classes.json"
# crop buffers a loader keeps: the batch being cropped, the two a prefetch
# queue holds and the one whose copy to the device is landing
_STAGING_BUFFERS = 4


def _resize_short_side(img: np.ndarray, size: int) -> np.ndarray:
    """Nearest-neighbor resize so the SHORT side equals ``size`` (aspect
    preserved) — the reference pipeline's canonicalization step."""
    h, w = img.shape[:2]
    if h <= w:
        nh, nw = size, max(size, int(round(w * size / h)))
    else:
        nh, nw = max(size, int(round(h * size / w))), size
    rows = np.minimum((np.arange(nh) * h / nh).astype(np.int64), h - 1)
    cols = np.minimum((np.arange(nw) * w / nw).astype(np.int64), w - 1)
    return img[rows][:, cols]


def _center_crop(img: np.ndarray, size: int) -> np.ndarray:
    h, w = img.shape[:2]
    oy, ox = (h - size) // 2, (w - size) // 2
    return img[oy : oy + size, ox : ox + size]


def _to_u8_rgb(img: np.ndarray, size: int) -> np.ndarray:
    """Decode-normalized float image (0..1) -> canonical [size, size, 3] u8."""
    img = _center_crop(_resize_short_side(img, size), size)
    if img.shape[-1] == 1:
        img = np.repeat(img, 3, axis=-1)
    return np.clip(img * 255.0 + 0.5, 0, 255).astype(np.uint8)


def pack_image_dir(
    src_dir: str, out_dir: str, *, size: int = 256, verbose: bool = False
) -> Dict[str, int]:
    """One-time preparation: directory tree -> packed u8 .npy per split.

    Input layout (reference convention): ``src_dir/<split>/<class>/*.png``.
    Writes ``<split>_images.npy`` ([n, size, size, 3] u8),
    ``<split>_labels.npy`` ([n] int32), ``classes.json`` and
    ``mean_rgb.json`` (channel means of the train split, 0..1 units).
    Returns per-split sample counts.
    """
    os.makedirs(out_dir, exist_ok=True)
    classes: list = []
    counts: Dict[str, int] = {}
    mean_acc, mean_n = np.zeros(3, np.float64), 0
    for split in SPLITS:
        split_dir = os.path.join(src_dir, split)
        if not os.path.isdir(split_dir):
            continue
        entries = []
        for cls in sorted(os.listdir(split_dir)):
            cls_dir = os.path.join(split_dir, cls)
            if not os.path.isdir(cls_dir):
                continue
            files = [
                os.path.join(cls_dir, f)
                for f in sorted(os.listdir(cls_dir))
                if f.lower().endswith(IMAGE_EXTENSIONS)
            ]
            if not files:
                continue
            if cls not in classes:
                classes.append(cls)
            entries.extend((p, classes.index(cls)) for p in files)
        if not entries:
            continue
        # np.lib.format + open_memmap: write incrementally, never hold the
        # whole split in RAM
        from numpy.lib.format import open_memmap

        images = open_memmap(
            os.path.join(out_dir, f"{split}_images.npy"),
            mode="w+", dtype=np.uint8, shape=(len(entries), size, size, 3),
        )
        labels = np.empty(len(entries), np.int32)
        for i, (path, label) in enumerate(entries):
            images[i] = _to_u8_rgb(_read_image(path), size)
            labels[i] = label
            if split == TRAIN:
                mean_acc += images[i].reshape(-1, 3).mean(axis=0) / 255.0
                mean_n += 1
            if verbose and (i + 1) % 1000 == 0:
                print(f"{split}: {i + 1}/{len(entries)}")
        images.flush()
        del images
        np.save(os.path.join(out_dir, f"{split}_labels.npy"), labels)
        counts[split] = len(entries)
    if not counts:
        raise FileNotFoundError(
            f"no {'/'.join(SPLITS)}/<class>/<image> files under {src_dir}"
        )
    with open(os.path.join(out_dir, CLASSES_FILE), "w") as f:
        json.dump(classes, f)
    mean_rgb = (mean_acc / max(mean_n, 1)).tolist() if mean_n else [0.5] * 3
    with open(os.path.join(out_dir, MEAN_FILE), "w") as f:
        json.dump(mean_rgb, f)
    return counts


class ImageNetLoader(PoolShardedMixin, Loader):
    """Packed-u8 image loader with reference augmentation semantics.

    ``data_dir`` holds the ``pack_image_dir`` output (or pass a raw image
    directory — it is packed into ``data_dir/.packed<size>`` on first use).
    Train minibatches are random ``crop_size`` crops with random horizontal
    flips; valid/test use the center crop.  Minibatch data stays uint8; the
    u8->f32 conversion and channel-mean subtraction run on-device
    (:meth:`device_preproc`).
    """

    def __init__(
        self,
        data_dir: str,
        *,
        crop_size: int = 227,
        pack_size: int = 256,
        random_flip: bool = True,
        mean_rgb: Optional[Tuple[float, float, float]] = None,
        mmap: bool = True,
        device_resident: bool = False,
        pool_sharded: bool = False,
        **kwargs,
    ):
        super().__init__(**kwargs)
        # device_resident: the PACKED u8 pool ships to HBM once
        # (device_context); per batch only [B, 4] int32 (index, oy, ox,
        # flip) crosses host->device and the random crop + flip + normalize
        # run inside the jitted step.  The TPU-first answer to a slow
        # host->device link for datasets that fit on-chip: steady-state
        # transfer drops from O(B * crop^2 * 3) bytes to O(B) — and the
        # tiny per-batch payloads enable the scanned epoch dispatch.
        self._device_resident = bool(device_resident)
        self.epoch_scan_friendly = self._device_resident
        # pool_sharded: shard the packed pool over the mesh's DATA axis —
        # REAL ImageNet (~150 GB packed at 256^2) can never fit one chip's
        # HBM; sharding multiplies capacity by the mesh size
        # (loader/pool_sharded.py has the full contract)
        if pool_sharded and not device_resident:
            raise ValueError("pool_sharded=True requires device_resident")
        self.wants_data_shards = pool_sharded
        self._mesh = None
        self._pool_offsets: Dict[str, int] = {}  # set after images load
        if not os.path.isdir(data_dir):
            raise FileNotFoundError(f"no such data_dir: {data_dir}")
        if not os.path.exists(os.path.join(data_dir, f"{TRAIN}_images.npy")):
            packed = os.path.join(data_dir, f".packed{pack_size}")
            if not os.path.exists(os.path.join(packed, f"{TRAIN}_images.npy")):
                pack_image_dir(data_dir, packed, size=pack_size)
            data_dir = packed
        self.data_dir = data_dir
        self.crop_size = int(crop_size)
        self.random_flip = random_flip
        self.images: Dict[str, np.ndarray] = {}
        self.labels: Dict[str, np.ndarray] = {}
        self._staging: list = []  # _staging_buffer()
        for split in SPLITS:
            ipath = os.path.join(data_dir, f"{split}_images.npy")
            if not os.path.exists(ipath):
                continue
            self.images[split] = np.load(
                ipath, mmap_mode="r" if mmap else None
            )
            self.labels[split] = np.load(
                os.path.join(data_dir, f"{split}_labels.npy")
            )
        if TRAIN not in self.images:
            raise FileNotFoundError(f"no train_images.npy under {data_dir}")
        h = self.images[TRAIN].shape[1]
        if self.crop_size > h:
            raise ValueError(
                f"crop_size {crop_size} exceeds packed image size {h}"
            )
        cpath = os.path.join(data_dir, CLASSES_FILE)
        self.classes = (
            json.load(open(cpath)) if os.path.exists(cpath) else None
        )
        if mean_rgb is None:
            mpath = os.path.join(data_dir, MEAN_FILE)
            mean_rgb = (
                tuple(json.load(open(mpath)))
                if os.path.exists(mpath)
                else (0.5, 0.5, 0.5)
            )
        self.mean_rgb = np.asarray(mean_rgb, np.float32)
        # offsets/concatenation ordering lives in ONE place: loader.base
        self._pool_offsets = base_pool_offsets(self.images)

    # -- Loader interface --------------------------------------------------
    @property
    def class_lengths(self) -> Dict[str, int]:
        return {k: len(v) for k, v in self.images.items()}

    @property
    def sample_shape(self) -> tuple:
        return (self.crop_size, self.crop_size, 3)

    def split_labels(self, split: str):
        return self.labels.get(split)

    def n_classes(self) -> int:
        return (
            len(self.classes)
            if self.classes is not None
            else int(self.labels[TRAIN].max()) + 1
        )

    def _crop_params(self, indices: np.ndarray, split: str):
        imgs = self.images[split]
        _, h, w, _ = imgs.shape
        cs = self.crop_size
        b = len(indices)
        if split == TRAIN:
            gen = prng.get(self.rand_name)
            oy = gen.integers(0, h - cs + 1, (b,)).astype(np.int64)
            ox = gen.integers(0, w - cs + 1, (b,)).astype(np.int64)
            flip = (
                gen.integers(0, 2, (b,)).astype(np.uint8)
                if self.random_flip
                else np.zeros(b, np.uint8)
            )
        else:
            oy = np.full(b, (h - cs) // 2, np.int64)
            ox = np.full(b, (w - cs) // 2, np.int64)
            flip = np.zeros(b, np.uint8)
        return oy, ox, flip

    @staticmethod
    def _timed(stage: str, fn, *args):
        """``fn(*args)`` under the span ``loader/<stage>`` and the stage
        label of the same name: a named part of the producer's fetch."""
        with observability.span(f"loader/{stage}"):
            t0 = time.perf_counter()
            try:
                return fn(*args)
            finally:
                _pipeline.stage_seconds().labels(stage=stage).observe(
                    time.perf_counter() - t0
                )

    def _staging_buffer(self, shape: tuple, dtype) -> np.ndarray:
        """Where one batch of crops is written: a buffer an earlier batch
        was cropped into, once NOTHING else refers to it, else a fresh
        ``np.empty`` (kept for later batches while the loader keeps fewer
        than ``_STAGING_BUFFERS``).  A fresh 158-633 MB allocation is
        mapped anew and zero-filled page by page under the crop threads'
        first writes; a kept one has its pages.

        The test is the array's reference count, which is what says that
        its memory is no one else's: the consumer's ``Minibatch``, a view
        of it, and jax all hold a reference for as long as they can read
        the bytes.  ``device_put`` keeps the array until its copy to the
        device has landed, and for the life of the device array where
        the backend ALIASES host memory in place of copying it (the CPU
        backend does, for an aligned array), so a batch a step has yet to
        read is never written over."""
        pool = self._staging
        counted = _pipeline.staging_buffers()
        for i in range(len(pool)):
            # 2: the list's reference and this call's argument
            if sys.getrefcount(pool[i]) == 2:
                if pool[i].shape == shape and pool[i].dtype == dtype:
                    counted.labels(source="recycled").inc()
                    return pool[i]
                del pool[i]  # a batch of another size: let it go
                break
        counted.labels(source="fresh").inc()
        buf = np.empty(shape, dtype)
        if len(pool) < _STAGING_BUFFERS:
            pool.append(buf)
        return buf

    def fill(self, indices: np.ndarray, split: str) -> Minibatch:
        oy, ox, flip = self._timed(
            _pipeline.STAGE_CROP_PARAMS, self._crop_params, indices, split
        )
        if self._device_resident:
            # [B, 4] int32 payload: pool row + crop offsets + flip bit —
            # the whole host->device transfer for this minibatch
            # (pool-sharded: the row is a LOCAL address into the owning
            # device's block)
            row = (
                self._local_addr(indices, split).astype(np.int64)
                if self.data_shards > 1
                else np.asarray(indices, np.int64)
                + self._pool_offsets[split]
            )
            data = np.stack(
                [row, oy, ox, flip.astype(np.int64)], axis=1
            ).astype(np.int32)
        else:
            from znicz_tpu.loader import native

            cs = self.crop_size
            # one native call, one span, whatever the batch: handing the
            # assembler row chunks for shorter spans cost 8-14 % of the
            # crop's wall (a thread spawn and a join barrier per chunk)
            images = self.images[split]
            data = self._timed(
                _pipeline.STAGE_CROP, native.crop_gather_u8,
                images, indices, oy, ox, flip, cs, cs,
                self._staging_buffer(
                    (len(indices), cs, cs, images.shape[-1]), images.dtype
                ),
            )
            # a library that did not build, or a CPU without the wide
            # flip, shows in one scrape
            crops = _pipeline.crop_images()
            unflipped, flipped = native.crop_paths(images)
            n_flipped = int(np.count_nonzero(flip))
            crops.labels(path=flipped).inc(n_flipped)
            crops.labels(path=unflipped).inc(len(flip) - n_flipped)
        return Minibatch(
            data=data,
            labels=self.labels[split][indices],
            targets=None,
            mask=None,
            indices=indices,
        )

    def _pool_split_arrays(self):
        return self.images

    def device_context(self):
        if not self._device_resident:
            return None
        if self.wants_data_shards:
            # only this process's shards' rows materialize from the mmap
            return {"pool": self._local_pool()}
        # one up-front transfer of the packed pool; base.pool_concat uses
        # the same ordering _pool_offsets was built from
        return {"pool": base_pool_concat(self.images)}

    def device_preproc(self):
        """u8 -> f32 in [-mean, 1-mean]: runs inside the jitted step.

        device_resident: the step receives [B, 4] (row, oy, ox, flip),
        gathers the packed rows from the HBM pool and crops/flips them
        with per-sample dynamic slices — augmentation at memory speed,
        fused into the XLA program."""
        import jax
        import jax.numpy as jnp

        mean = tuple(float(m) for m in self.mean_rgb)

        if not self._device_resident:

            def pre(x, ctx):
                return x.astype(jnp.float32) * (1.0 / 255.0) - jnp.asarray(
                    mean, jnp.float32
                )

            return pre

        cs = self.crop_size

        def crop_batch(payload, pool):
            # slice each crop STRAIGHT out of the pool (one batched
            # dynamic_slice, no [B, H, W, 3] full-row intermediate):
            # measured 8.9 -> 7.6 ms/step at B=1024 on v5e vs the
            # gather-rows-then-crop form.  Flip stays the where+reverse
            # select — every index-vector-gather reformulation measured
            # 3x SLOWER (BASELINE.md round-5 crop-path table).
            def crop_one(row, y, x, f):
                c = jax.lax.dynamic_slice(
                    pool, (row, y, x, 0), (1, cs, cs, 3)
                )[0]
                return jnp.where(f > 0, c[:, ::-1], c)

            crops = jax.vmap(crop_one)(
                payload[:, 0], payload[:, 1], payload[:, 2], payload[:, 3]
            )
            return crops.astype(jnp.float32) * (1.0 / 255.0) - jnp.asarray(
                mean, jnp.float32
            )

        if self.wants_data_shards:
            # payload rows and pool rows are both device-local: the whole
            # gather+crop+normalize runs per-shard inside a shard_map
            return self._shard_map_pre(crop_batch)

        def pre(payload, ctx):
            return crop_batch(payload, ctx["pool"])

        return pre
