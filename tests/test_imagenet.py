"""ImageNet pipeline tests: pack, crop/flip augmentation, device-side
normalize, end-to-end training on disk-backed images.

Covers the reference ImageNet loader pipeline semantics [SURVEY.md 2.3
"Znicz loaders": resize / random crop + flip / mean subtract / eval center
crop] through the TPU-first rebuild (``znicz_tpu/loader/imagenet.py``).
"""

import os
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest

from znicz_tpu.core import prng
from znicz_tpu.loader import ImageNetLoader, native, pack_image_dir
from znicz_tpu.loader.datasets import imagenet_synthetic
from znicz_tpu.workflow import StandardWorkflow


def _counted(metric: str) -> dict:
    """{label value: count} of one labelled counter of the registry."""
    from znicz_tpu.observability import get_registry

    fam = get_registry().metrics().get(metric)
    if fam is None:
        return {}
    return {k[0]: c.value for k, c in fam.children().items()}


def _write_png(path, arr_u8):
    import matplotlib.image as mpimg

    mpimg.imsave(path, arr_u8)


@pytest.fixture(scope="module")
def image_dir(tmp_path_factory):
    """Tiny 2-class image tree with varied sizes (exercises short-side
    resize); class 0 is dark, class 1 is bright — linearly separable."""
    gen = np.random.default_rng(7)
    root = tmp_path_factory.mktemp("imgs")
    sizes = [(40, 56), (64, 40), (48, 48), (56, 44)]
    for split, n in (("train", 16), ("valid", 8)):
        for cls, base in (("dark", 60), ("bright", 190)):
            d = root / split / cls
            d.mkdir(parents=True)
            for i in range(n):
                h, w = sizes[i % len(sizes)]
                img = np.clip(
                    base + gen.normal(0, 25, (h, w, 3)), 0, 255
                ).astype(np.uint8)
                _write_png(str(d / f"{i:03d}.png"), img)
    return str(root)


@pytest.fixture(scope="module")
def packed_dir(image_dir, tmp_path_factory):
    out = str(tmp_path_factory.mktemp("packed"))
    counts = pack_image_dir(image_dir, out, size=32)
    assert counts == {"train": 32, "valid": 16}
    return out


class TestPack:
    def test_packed_files_and_shapes(self, packed_dir):
        imgs = np.load(os.path.join(packed_dir, "train_images.npy"))
        labs = np.load(os.path.join(packed_dir, "train_labels.npy"))
        assert imgs.shape == (32, 32, 32, 3) and imgs.dtype == np.uint8
        assert labs.shape == (32,) and set(labs) == {0, 1}
        assert os.path.exists(os.path.join(packed_dir, "mean_rgb.json"))

    def test_mean_is_plausible(self, packed_dir):
        import json

        mean = json.load(open(os.path.join(packed_dir, "mean_rgb.json")))
        # dark(60) and bright(190) classes average near 125/255 ~ 0.49
        assert all(0.3 < m < 0.7 for m in mean)

    def test_class_brightness_separation(self, packed_dir):
        imgs = np.load(os.path.join(packed_dir, "train_images.npy"))
        labs = np.load(os.path.join(packed_dir, "train_labels.npy"))
        # classes.json order is directory order: bright=0, dark=1
        bright = imgs[labs == 0].mean()
        dark = imgs[labs == 1].mean()
        assert bright > dark + 50


class TestCropGather:
    def test_native_matches_numpy(self):
        gen = np.random.default_rng(3)
        data = gen.integers(0, 256, (10, 16, 20, 3)).astype(np.uint8)
        idx = gen.integers(0, 10, (6,)).astype(np.int64)
        oy = gen.integers(0, 16 - 8 + 1, (6,)).astype(np.int64)
        ox = gen.integers(0, 20 - 12 + 1, (6,)).astype(np.int64)
        flip = np.array([0, 1, 0, 1, 1, 0], np.uint8)
        out = native.crop_gather_u8(data, idx, oy, ox, flip, 8, 12)
        assert out.shape == (6, 8, 12, 3) and out.dtype == np.uint8
        for i in range(6):
            win = data[idx[i], oy[i] : oy[i] + 8, ox[i] : ox[i] + 12]
            exp = win[:, ::-1] if flip[i] else win
            np.testing.assert_array_equal(out[i], exp)

    @pytest.mark.parametrize("flip", [0, 1])
    @pytest.mark.parametrize("at_right_edge", [False, True])
    @pytest.mark.parametrize("out_w", [1, 4, 5, 6, 16, 227])
    @pytest.mark.parametrize("c", [1, 3, 4])
    def test_every_row_width_and_pixel_size(self, c, out_w, at_right_edge, flip):
        """The flipped row's wide path (c == 3: sixteen bytes a turn, the
        tail pixel by pixel) and the general one, at row widths around
        the turn's five pixels, at both edges of the image."""
        w = max(out_w, 16) + 13
        data = np.random.default_rng(c * 1000 + out_w).integers(
            0, 256, (3, 9, w, c), dtype=np.uint8
        )
        idx = np.array([2, 0, 2], np.int64)
        oy = np.array([0, 2, 1], np.int64)
        ox = np.full(3, w - out_w if at_right_edge else 0, np.int64)
        # the bytes around every row must stay as they were
        out = np.full((3, 7, out_w, c), 0xA5, np.uint8)
        got = native.crop_gather_u8(
            data, idx, oy, ox, np.full(3, flip, np.uint8), 7, out_w, out=out
        )
        assert got is out
        for i in range(3):
            win = data[idx[i], oy[i] : oy[i] + 7, ox[i] : ox[i] + out_w]
            np.testing.assert_array_equal(got[i], win[:, ::-1] if flip else win)

    def test_no_access_outside_the_window_or_the_output(self, tmp_path):
        """Flipped crops at the two places where one byte too far faults:
        the bottom-right window of the LAST image of a memory-mapped file
        that ends at the image's last byte (a load past it is a SIGBUS)
        written into the last bytes of an allocation, and the top-left
        window of the first image written into an allocation's first
        bytes, with a protected page on the far side of each (SIGSEGV).
        Every row width from 1 to 22 pixels, so every length of the wide
        loop's tail; in a process of its own, which a fault would kill."""
        script = textwrap.dedent(
            """
            import ctypes, mmap, os, sys
            import numpy as np
            from znicz_tpu.loader import native

            page = mmap.PAGESIZE
            libc = ctypes.CDLL(None, use_errno=True)
            libc.mprotect.argtypes = [ctypes.c_void_p, ctypes.c_size_t, ctypes.c_int]
            keep = []

            def guarded(nbytes, at_end):
                # [no access][pages][no access], the array at one end
                pages = -(-nbytes // page)
                buf = mmap.mmap(-1, (pages + 2) * page)
                keep.append(buf)
                addr = ctypes.addressof(ctypes.c_char.from_buffer(buf))
                for guard in (addr, addr + (pages + 1) * page):
                    assert libc.mprotect(guard, page, 0) == 0  # PROT_NONE
                start = page + (pages * page - nbytes if at_end else 0)
                return np.frombuffer(buf, np.uint8, nbytes, start)

            path, c = sys.argv[1], int(sys.argv[2])
            n, h, w = 2, 32, 32 * page // 1024
            size = n * h * w * c
            assert size % page == 0 and native.available()
            pixels = np.random.default_rng(c).integers(0, 256, size, dtype=np.uint8)
            with open(path, "wb") as f:
                f.write(pixels.tobytes())
                f.write(bytes(page))
            mapped = np.memmap(path, np.uint8, "r", shape=(size + page,))
            # the file now ends where the last image ends: the map's last
            # page has nothing behind it
            os.truncate(path, size)
            last = mapped[:size].reshape(n, h, w, c)
            first = guarded(size, at_end=False)
            first[:] = pixels
            first = first.reshape(n, h, w, c)
            want = pixels.reshape(n, h, w, c)
            one = lambda v: np.array([v], np.int64)
            for crop in range(1, 23):
                shape = (1, crop, crop, c)
                out = guarded(crop * crop * c, at_end=True).reshape(shape)
                native.crop_gather_u8(
                    last, one(n - 1), one(h - crop), one(w - crop),
                    np.array([1], np.uint8), crop, crop, out=out,
                )
                assert np.array_equal(
                    out[0], want[n - 1, h - crop :, w - crop :][:, ::-1]
                ), crop
                out = guarded(crop * crop * c, at_end=False).reshape(shape)
                native.crop_gather_u8(
                    first, one(0), one(0), one(0),
                    np.array([1], np.uint8), crop, crop, out=out,
                )
                assert np.array_equal(out[0], want[0, :crop, :crop][:, ::-1]), crop
            print("cropped", native.crop_paths(last)[1])
            """
        )
        env = dict(os.environ, PYTHONPATH=os.pathsep.join(sys.path))
        for c in (3, 4):
            done = subprocess.run(
                [sys.executable, "-c", script, str(tmp_path / f"c{c}.bin"), str(c)],
                capture_output=True, text=True, timeout=120, env=env,
            )
            assert done.returncode == 0, (c, done.returncode, done.stderr[-2000:])
            assert "cropped" in done.stdout

    def test_out_must_fit(self):
        data = np.zeros((2, 8, 8, 3), np.uint8)
        one = np.zeros(1, np.int64)
        for bad in (
            np.empty((1, 4, 4, 4), np.uint8),
            np.empty((1, 4, 4, 3), np.int8),
            np.empty((1, 4, 8, 3), np.uint8)[:, :, ::2],
        ):
            with pytest.raises(ValueError):
                native.crop_gather_u8(data, one, one, one, one, 4, 4, out=bad)

    def test_out_of_bounds_rejected(self):
        data = np.zeros((2, 8, 8, 3), np.uint8)
        with pytest.raises(IndexError):
            native.crop_gather_u8(
                data, np.array([0]), np.array([5]), np.array([0]),
                np.array([0], np.uint8), 4, 4,
            )
        with pytest.raises(IndexError):
            native.crop_gather_u8(
                data, np.array([2]), np.array([0]), np.array([0]),
                np.array([0], np.uint8), 4, 4,
            )


class TestImageNetLoader:
    def test_train_batches_are_u8_crops(self, packed_dir):
        loader = ImageNetLoader(packed_dir, crop_size=27, minibatch_size=8)
        mb = next(iter(loader.batches("train")))
        assert mb.data.shape == (8, 27, 27, 3)
        assert mb.data.dtype == np.uint8
        assert loader.sample_shape == (27, 27, 3)

    def test_eval_center_crop_deterministic(self, packed_dir):
        loader = ImageNetLoader(packed_dir, crop_size=27, minibatch_size=8)
        a = [mb.data for mb in loader.batches("valid", shuffle=False)]
        b = [mb.data for mb in loader.batches("valid", shuffle=False)]
        for x, y in zip(a, b):
            np.testing.assert_array_equal(x, y)

    def test_train_crops_vary(self, packed_dir):
        prng.seed_all(11)
        loader = ImageNetLoader(packed_dir, crop_size=27, minibatch_size=32)
        a = next(iter(loader.batches("train", shuffle=False))).data
        b = next(iter(loader.batches("train", shuffle=False))).data
        # same order (no shuffle) but fresh random crops: batches differ
        assert not np.array_equal(a, b)

    def test_crops_are_counted_by_the_path_they_took(self, packed_dir, monkeypatch):
        from znicz_tpu.observability import pipeline

        def counted():
            return _counted(pipeline.CROP_IMAGES_METRIC)

        prng.seed_all(5)
        loader = ImageNetLoader(packed_dir, crop_size=27, minibatch_size=8)
        before = counted()
        flips = 0
        real_params = loader._crop_params

        def spy(indices, split):
            nonlocal flips
            oy, ox, flip = real_params(indices, split)
            flips += int(flip.sum())
            return oy, ox, flip

        monkeypatch.setattr(loader, "_crop_params", spy)
        images = sum(len(mb.data) for _, mb in loader.epoch())
        delta = {
            k: v - before.get(k, 0)
            for k, v in counted().items()
            if v != before.get(k, 0)
        }
        unflipped, flipped = native.crop_paths(loader.images["train"])
        # flip_wide on an x86-64 host with SSSE3, flip_pixel elsewhere
        assert unflipped == "copy" and flipped in ("flip_wide", "flip_pixel")
        assert 0 < flips < images
        assert delta == {flipped: flips, "copy": images - flips}
        # a library that is not there: every image says so
        monkeypatch.setattr(native, "_build_and_load", lambda: None)
        before = counted()
        mb = next(iter(loader.batches("train", shuffle=False)))
        assert counted()["numpy"] - before.get("numpy", 0) == len(mb.data)

    def test_device_preproc_subtracts_mean(self, packed_dir):
        loader = ImageNetLoader(
            packed_dir, crop_size=27, minibatch_size=8,
            mean_rgb=(0.25, 0.5, 0.75),
        )
        pre = loader.device_preproc()
        x = np.full((2, 27, 27, 3), 255, np.uint8)
        out = np.asarray(pre(jnp.asarray(x), None))
        np.testing.assert_allclose(
            out[0, 0, 0], [0.75, 0.5, 0.25], atol=1e-6
        )

    def test_device_resident_matches_native_crops(self, packed_dir):
        # the on-device crop+flip+normalize must produce EXACTLY what the
        # native host path produces given the same PRNG draws
        import jax

        def batch(device_resident):
            prng.seed_all(42)
            loader = ImageNetLoader(
                packed_dir, crop_size=27, minibatch_size=8,
                device_resident=device_resident,
            )
            mb = next(iter(loader.batches("train", shuffle=False)))
            pre = loader.device_preproc()
            ctx_host = loader.device_context()
            ctx = None if ctx_host is None else jax.device_put(ctx_host)
            return np.asarray(pre(jnp.asarray(mb.data), ctx)), mb

        host, mb_h = batch(False)
        dev, mb_d = batch(True)
        assert mb_d.data.shape == (8, 4)  # [B, (row, oy, ox, flip)] only
        assert mb_d.data.dtype == np.int32
        np.testing.assert_array_equal(mb_h.labels, mb_d.labels)
        np.testing.assert_allclose(host, dev, atol=1e-6)

    def test_device_resident_eval_center_crop(self, packed_dir):
        import jax

        prng.seed_all(7)
        loader = ImageNetLoader(
            packed_dir, crop_size=27, minibatch_size=8,
            device_resident=True,
        )
        assert loader.epoch_scan_friendly
        pre = loader.device_preproc()
        ctx = jax.device_put(loader.device_context())
        a = [
            np.asarray(pre(jnp.asarray(mb.data), ctx))
            for mb in loader.batches("valid", shuffle=False)
        ]
        prng.seed_all(99)  # eval crops must not depend on the PRNG
        b = [
            np.asarray(pre(jnp.asarray(mb.data), ctx))
            for mb in loader.batches("valid", shuffle=False)
        ]
        for x, y in zip(a, b):
            np.testing.assert_array_equal(x, y)

    def test_device_resident_trains_end_to_end(self, packed_dir):
        from znicz_tpu.workflow import StandardWorkflow

        prng.seed_all(13)
        loader = ImageNetLoader(
            packed_dir, crop_size=27, minibatch_size=8,
            device_resident=True,
        )
        wf = StandardWorkflow(
            loader,
            [
                {"type": "conv_relu", "->": {"n_kernels": 8, "kx": 5,
                                             "ky": 5}},
                {"type": "max_pooling", "->": {"kx": 2, "ky": 2}},
                {"type": "softmax", "->": {"output_sample_shape": 3}},
            ],
            decision_config={"max_epochs": 2},
            default_hyper={"learning_rate": 0.05, "gradient_moment": 0.9},
        )
        wf.initialize(seed=13)
        verdict = wf.run_epoch()
        assert np.isfinite(verdict["summary"]["train"]["loss"])

    def test_pool_sharded_matches_host_crops(self, packed_dir):
        # data-axis-sharded pool: the shard_map gather+crop must produce
        # EXACTLY the native host crops for the same indices and draws
        # (payload carries the draws, so this is closed-loop)
        import jax

        from znicz_tpu.loader import native
        from znicz_tpu.parallel import DataParallel, make_mesh

        prng.seed_all(41)
        loader = ImageNetLoader(
            packed_dir, crop_size=27, minibatch_size=16,
            device_resident=True, pool_sharded=True,
        )
        loader.set_data_shards(8)
        ctx = loader.place_device_context(DataParallel(make_mesh(8, 1)))
        # each device holds 1/8 of train+valid rows — the capacity win
        assert ctx["pool"].shape[0] == 48
        assert ctx["pool"].addressable_shards[0].data.shape[0] == 6
        pre = loader.device_preproc()
        for split in ("train", "valid"):
            for mb in loader.batches(split, shuffle=False):
                out = np.asarray(pre(jnp.asarray(mb.data), ctx))
                exp_u8 = native.crop_gather_u8(
                    loader.images[split], mb.indices,
                    mb.data[:, 1].astype(np.int64),
                    mb.data[:, 2].astype(np.int64),
                    mb.data[:, 3].astype(np.uint8), 27, 27,
                )
                exp = (
                    exp_u8.astype(np.float32) / 255.0
                    - loader.mean_rgb
                )
                np.testing.assert_allclose(out, exp, atol=1e-6)

    def test_pool_sharded_trains_end_to_end(self, packed_dir):
        from znicz_tpu.parallel import DataParallel, make_mesh
        from znicz_tpu.workflow import StandardWorkflow

        prng.seed_all(17)
        loader = ImageNetLoader(
            packed_dir, crop_size=27, minibatch_size=16,
            device_resident=True, pool_sharded=True,
        )
        wf = StandardWorkflow(
            loader,
            [
                {"type": "conv_relu", "->": {"n_kernels": 8, "kx": 5,
                                             "ky": 5}},
                {"type": "max_pooling", "->": {"kx": 2, "ky": 2}},
                {"type": "softmax", "->": {"output_sample_shape": 3}},
            ],
            decision_config={"max_epochs": 2},
            default_hyper={"learning_rate": 0.05, "gradient_moment": 0.9},
            parallel=DataParallel(make_mesh(8, 1)),
        )
        wf.initialize(seed=17)
        assert wf._use_epoch_scan()
        verdict = wf.run_epoch()
        assert verdict["summary"]["train"]["n_samples"] == 32
        assert np.isfinite(verdict["summary"]["train"]["loss"])

    def test_raw_image_dir_autopacks(self, image_dir):
        loader = ImageNetLoader(
            image_dir, crop_size=24, pack_size=28, minibatch_size=8
        )
        assert os.path.exists(
            os.path.join(image_dir, ".packed28", "train_images.npy")
        )
        mb = next(iter(loader.batches("train")))
        assert mb.data.shape == (8, 24, 24, 3)

    def test_crop_larger_than_pack_rejected(self, packed_dir):
        with pytest.raises(ValueError):
            ImageNetLoader(packed_dir, crop_size=64, minibatch_size=8)


class TestStagingBuffers:
    """Crops are written into buffers the loader keeps, and a buffer is
    written again only when nothing else can read it."""

    @staticmethod
    def _counted():
        from znicz_tpu.observability import pipeline

        return _counted(pipeline.STAGING_BUFFERS_METRIC)

    def _fill(self, loader):
        return loader.fill(np.arange(8) % 32, "train").data

    def _since(self, before: dict) -> dict:
        now = self._counted()
        return {k: v - before.get(k, 0) for k, v in now.items() if v != before.get(k, 0)}

    def test_a_buffer_something_refers_to_is_not_handed_out(self, packed_dir):
        loader = ImageNetLoader(packed_dir, crop_size=27, minibatch_size=8)
        before = self._counted()
        held = [self._fill(loader) for _ in range(6)]
        # six batches alive at once: six buffers, the loader keeps four
        assert len({b.ctypes.data for b in held}) == 6
        assert len(loader._staging) == 4
        assert self._since(before) == {"fresh": 6}
        copies = [b.copy() for b in held]
        # a VIEW of a batch holds its buffer as the batch itself does
        first_row = held[0][0]
        addresses = {b.ctypes.data for b in held}
        kept = {b.ctypes.data for b in loader._staging}
        del held
        again = [self._fill(loader) for _ in range(3)]
        assert {b.ctypes.data for b in again} <= kept - {first_row.ctypes.data}
        assert {b.ctypes.data for b in again} <= addresses
        np.testing.assert_array_equal(first_row, copies[0][0])
        assert self._since(before) == {"fresh": 6, "recycled": 3}
        # all four in use again (three batches and the view): a fresh one
        extra = self._fill(loader)
        assert extra.ctypes.data not in kept
        del first_row, again
        assert self._fill(loader).ctypes.data in kept

    def test_another_batch_size_lets_a_buffer_go(self, packed_dir):
        loader = ImageNetLoader(packed_dir, crop_size=27, minibatch_size=8)
        assert self._fill(loader).shape == (8, 27, 27, 3)
        small = loader.fill(np.arange(4), "train").data
        assert small.shape == (4, 27, 27, 3) and small.flags["OWNDATA"]
        assert [b.shape[0] for b in loader._staging] == [4]

    def test_a_batch_in_flight_to_the_device_is_not_written_over(
        self, packed_dir, monkeypatch
    ):
        """Batches are placed and dropped at once, as the prefetch
        producer does, and only the device arrays are kept: each must
        still hold ITS crops when all are read at the end, whether jax
        copied the buffer (and had it until the copy landed) or aliased
        it (and has it for as long as the device array lives)."""
        import jax

        prng.seed_all(9)
        loader = ImageNetLoader(packed_dir, crop_size=27, minibatch_size=8)
        drawn = []
        real_params = loader._crop_params

        def spy(indices, split):
            params = real_params(indices, split)
            drawn.append((np.array(indices),) + params)
            return params

        monkeypatch.setattr(loader, "_crop_params", spy)
        placed = [jax.device_put(self._fill(loader)) for _ in range(24)]
        images = loader.images["train"]
        for x, (idx, oy, ox, flip) in zip(placed, drawn):
            want = native.crop_gather_u8(images, idx, oy, ox, flip, 27, 27)
            np.testing.assert_array_equal(np.asarray(x), want)
        # the loader went round its buffers meanwhile, or jax kept them all
        counted = self._counted()
        assert counted.get("recycled", 0) + counted.get("fresh", 0) >= 24

    def test_a_buffer_a_device_array_aliases_is_not_recycled(self, packed_dir):
        """The CPU backend takes an aligned host array as the device
        array's memory.  Such a buffer stays out of circulation until the
        device array is gone."""
        import jax

        loader = ImageNetLoader(packed_dir, crop_size=27, minibatch_size=8)
        shape, spare = (8, 27, 27, 3), []
        for _ in range(512):
            buf = np.empty(shape, np.uint8)
            if buf.ctypes.data % 64 == 0:
                break
            spare.append(buf)  # held, so the allocator moves on
        else:
            pytest.skip("no 64-byte aligned allocation came up")
        del spare
        address = buf.ctypes.data
        loader._staging.append(buf)
        del buf
        batch = self._fill(loader)
        assert batch.ctypes.data == address  # recycled: nothing held it
        x = jax.device_put(batch)
        x.block_until_ready()
        if x.unsafe_buffer_pointer() != address:
            pytest.skip("this backend copied the aligned array")
        want = batch.copy()
        del batch
        for _ in range(6):
            assert self._fill(loader).ctypes.data != address
        np.testing.assert_array_equal(np.asarray(x), want)
        del x
        for _ in range(6):  # jax lets go of host arrays at a later call
            jax.device_put(np.zeros(3)).block_until_ready()
            if self._fill(loader).ctypes.data == address:
                break
        else:
            pytest.fail("the buffer never came back after its alias died")


class TestEndToEnd:
    def test_train_on_disk_images_converges(self, packed_dir):
        prng.seed_all(42)
        loader = ImageNetLoader(packed_dir, crop_size=27, minibatch_size=16)
        wf = StandardWorkflow(
            loader,
            [
                {"type": "conv_relu",
                 "->": {"n_kernels": 8, "kx": 5, "ky": 5, "sliding": (2, 2)}},
                {"type": "max_pooling", "->": {"kx": 2, "ky": 2}},
                {"type": "softmax", "->": {"output_sample_shape": 2}},
            ],
            decision_config={"max_epochs": 6},
            default_hyper={"learning_rate": 0.05, "gradient_moment": 0.9},
        )
        wf.initialize(seed=42)
        dec = wf.run()
        first = dec.history[0]["train"]["loss"]
        last = dec.history[-1]["train"]["loss"]
        assert last < first
        # brightness-separable task: the net must actually learn it
        assert dec.history[-1]["valid"]["err_pct"] <= 25.0

    def test_u8_device_path_matches_f32_path(self):
        """imagenet_synthetic(store_u8) trains identically (up to
        quantization) to an eagerly-normalized f32 loader on the same data."""
        prng.seed_all(5)
        u8_loader = imagenet_synthetic(
            image_size=16, n_classes=4, n_train=64, n_valid=0,
            minibatch_size=32,
        )
        mb = next(iter(u8_loader.batches("train", shuffle=False)))
        assert mb.data.dtype == np.uint8
        pre = u8_loader.device_preproc()
        assert pre is not None
        x_dev = np.asarray(pre(jnp.asarray(mb.data), None))
        x_host = mb.data.astype(np.float32) / 255.0 - 0.5
        np.testing.assert_allclose(x_dev, x_host, atol=1e-6)

    def test_alexnet_uses_imagenet_loader_with_data_dir(self, image_dir):
        from znicz_tpu.core.config import root
        from znicz_tpu.models import alexnet

        prng.seed_all(1)
        saved = root.alexnet.to_dict()
        try:
            # raw image dir: auto-packs at 256, trains at the real 227 crop
            root.alexnet.loader.update(
                {"data_dir": image_dir, "minibatch_size": 8}
            )
            wf = alexnet.build_workflow()
        finally:
            root.alexnet.clear()
            root.alexnet.update(saved)
        assert isinstance(wf.loader, ImageNetLoader)
        assert wf.loader.sample_shape == (227, 227, 3)
        # head resized to the dataset's 2 classes
        assert wf.model.output_shape == (2,)
        mb = next(iter(wf.loader.batches("train")))
        assert mb.data.dtype == np.uint8 and mb.data.shape[1:] == (227, 227, 3)
