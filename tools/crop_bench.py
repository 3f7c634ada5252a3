#!/usr/bin/env python3
"""What one host crop costs, on whatever host this runs: run by hand.

``native.crop_gather_u8`` (``native/batch_assembler.cc``) over a packed
``train_images.npy``, by batch size, by the share of flipped images and
by where the crops are written: a ``fresh`` ``np.empty`` every call (the
last three kept alive, as a prefetch queue keeps them) or one ``reused``
buffer.  Each row prints the call's wall (median and least of the calls
that fit ``--seconds``), microseconds an image on the wall, and the same
times the threads the library starts for that batch
(``min(hardware_concurrency(), batch)``): thread time an image, which is
what a faster core, a fault-free output or an even split would lower.
``batch 1`` is one thread: a core's own speed, with the ``empty call``
row (one 1x1 crop) as the price of starting and joining it.

    python tools/crop_bench.py                       # a 2,048-image stand-in
    python tools/crop_bench.py --data .bench_cache/packed-16384x256/train_images.npy

Beside a training cell (PERF.md section 6, PR 32): start the cell in the
background, wait for its "set-up inside the driver" line, then run this
with ``JAX_PLATFORMS=cpu`` (it never touches a device) for the batch the
cell crops.  Nothing imports this file.
"""

from __future__ import annotations

import argparse
import collections
import os
import statistics
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from znicz_tpu.loader import native  # noqa: E402


def _stand_in(directory: str, n: int, size: int) -> np.ndarray:
    from numpy.lib.format import open_memmap

    path = os.path.join(directory, "train_images.npy")
    images = open_memmap(path, mode="w+", dtype=np.uint8, shape=(n, size, size, 3))
    rng = np.random.default_rng(20120930)
    for lo in range(0, n, 256):
        images[lo : lo + 256] = rng.integers(
            0, 256, (min(256, n - lo), size, size, 3), dtype=np.uint8
        )
    images.flush()
    del images
    return np.load(path, mmap_mode="r")


def _time_calls(call, seconds: float) -> list:
    call()  # the first call maps the pages it reads and writes
    walls, t_end = [], time.perf_counter() + seconds
    while len(walls) < 3 or time.perf_counter() < t_end:
        t0 = time.perf_counter()
        call()
        walls.append(time.perf_counter() - t0)
    return walls


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--data", help="a packed [N, H, W, 3] uint8 .npy (memory-mapped)")
    ap.add_argument("--images", type=int, default=2048, help="stand-in size without --data")
    ap.add_argument("--size", type=int, default=256, help="stand-in image side")
    ap.add_argument("--crop", type=int, default=227)
    ap.add_argument("--batches", type=int, nargs="+", default=[1, 64, 1024, 4096])
    ap.add_argument("--flips", type=float, nargs="+", default=[0.0, 0.5, 1.0])
    ap.add_argument("--outputs", nargs="+", default=["fresh", "reused"],
                    choices=["fresh", "reused"])
    ap.add_argument("--seconds", type=float, default=1.0, help="a row's timed calls")
    ap.add_argument("--seed", type=int, default=32)
    args = ap.parse_args()

    if not native.available():
        print("the native library did not build: nothing to time", file=sys.stderr)
        return 1
    with tempfile.TemporaryDirectory() as tmp:
        data = (
            np.load(args.data, mmap_mode="r") if args.data
            else _stand_in(tmp, args.images, args.size)
        )
        n, h, w, c = data.shape
        for lo in range(0, n, 256):  # map the file before any row is timed
            data[lo : lo + 256].max()
        hw = os.cpu_count() or 4
        print(
            f"host: {hw} cpus, affinity {len(os.sched_getaffinity(0))}, load "
            f"{os.getloadavg()[0]:.1f}; data {data.shape} "
            f"{'(' + args.data + ')' if args.data else '(stand-in)'}; crop "
            f"{args.crop}; flipped rows: "
            f"{native.crop_paths(data)[1]}",
            flush=True,
        )
        rng = np.random.default_rng(args.seed)
        one = np.zeros(1, np.int64)
        walls = _time_calls(
            lambda: native.crop_gather_u8(data, one, one, one, one, 1, 1), 0.2
        )
        print(f"empty call (one thread started and joined): "
              f"{statistics.median(walls) * 1e6:.0f} us", flush=True)
        print("batch  flipped  output  calls  wall_ms_median  wall_ms_least  "
              "us_per_image  thread_us_per_image", flush=True)
        for batch in args.batches:
            idx = rng.integers(0, n, batch).astype(np.int64)
            oy = rng.integers(0, h - args.crop + 1, batch).astype(np.int64)
            ox = rng.integers(0, w - args.crop + 1, batch).astype(np.int64)
            threads = min(hw, batch)
            for share in args.flips:
                flip = (np.arange(batch) < round(share * batch)).astype(np.uint8)
                rng.shuffle(flip)
                for output in args.outputs:
                    if output == "reused":
                        buf = np.empty((batch, args.crop, args.crop, c), np.uint8)
                        call = lambda: native.crop_gather_u8(  # noqa: E731
                            data, idx, oy, ox, flip, args.crop, args.crop, out=buf
                        )
                    else:
                        kept = collections.deque(maxlen=3)
                        call = lambda: kept.append(  # noqa: E731
                            native.crop_gather_u8(
                                data, idx, oy, ox, flip, args.crop, args.crop
                            )
                        )
                    walls = _time_calls(call, args.seconds)
                    med = statistics.median(walls)
                    print(
                        f"{batch:5d}  {share:7.2f}  {output:6s}  {len(walls):5d}  "
                        f"{med * 1e3:14.3f}  {min(walls) * 1e3:13.3f}  "
                        f"{med / batch * 1e6:12.1f}  "
                        f"{med * threads / batch * 1e6:19.1f}",
                        flush=True,
                    )
        del data
    return 0


if __name__ == "__main__":
    sys.exit(main())
