"""Tests for the loader layer: split bookkeeping, masking, shuffling."""

import numpy as np
import pytest

from znicz_tpu.core import prng
from znicz_tpu.loader import FullBatchLoader, datasets, normalizers
from znicz_tpu.loader.base import split_sizes


def _loader(n_train=25, bs=10, **kw):
    x = np.arange(n_train * 4, dtype=np.float32).reshape(n_train, 4)
    y = np.arange(n_train, dtype=np.int32) % 3
    return FullBatchLoader({"train": x}, {"train": y}, minibatch_size=bs, **kw)


class TestFullBatchLoader:
    def test_static_shapes_and_mask(self):
        ld = _loader(25, 10, shuffle=False)
        batches = list(ld.batches("train"))
        assert len(batches) == 3
        for mb in batches:
            assert mb.data.shape == (10, 4)
            assert mb.mask.shape == (10,)
        # last batch: 5 valid rows
        assert batches[-1].mask.sum() == 5.0
        assert batches[0].mask.sum() == 10.0

    def test_covers_all_samples_once(self):
        ld = _loader(25, 10)
        seen = []
        for mb in ld.batches("train"):
            seen.extend(mb.indices[mb.mask > 0].tolist())
        assert sorted(seen) == list(range(25))

    def test_shuffle_changes_order_deterministically(self):
        prng.seed_all(7)
        ld = _loader(25, 25)
        first = next(iter(ld.batches("train"))).indices.copy()
        second = next(iter(ld.batches("train"))).indices.copy()
        assert not np.array_equal(first, second)  # reshuffled between epochs
        # same seed -> same orders
        prng.seed_all(7)
        ld2 = _loader(25, 25)
        np.testing.assert_array_equal(
            next(iter(ld2.batches("train"))).indices, first
        )

    def test_labels_follow_indices(self):
        ld = _loader(12, 5)
        for mb in ld.batches("train"):
            np.testing.assert_array_equal(mb.labels, mb.indices % 3)

    def test_epoch_iterates_splits(self):
        x = np.zeros((8, 2), np.float32)
        ld = FullBatchLoader(
            {"train": x, "valid": x[:4], "test": x[:2]},
            {"train": np.zeros(8, np.int32)},
            minibatch_size=4,
        )
        tags = [split for split, _ in ld.epoch()]
        assert tags == ["train", "train", "valid", "test"]
        assert ld.epoch_number == 1

    def test_state_roundtrip(self):
        ld = _loader(25, 10)
        list(ld.batches("train"))
        state = ld.state_dict()
        ld2 = _loader(25, 10)
        ld2.load_state_dict(state)
        np.testing.assert_array_equal(
            ld._split_order("train"), ld2._split_order("train")
        )

    def test_normalization_mean_disp(self):
        ld = _loader(20, 20, normalization="mean_disp", shuffle=False)
        mb = next(iter(ld.batches("train")))
        np.testing.assert_allclose(mb.data.mean(axis=0), 0.0, atol=1e-5)
        np.testing.assert_allclose(mb.data.std(axis=0), 1.0, atol=1e-4)


class TestNormalizers:
    def test_linear_range(self):
        data = np.array([[0.0, 10.0], [5.0, 20.0]], np.float32)
        st = normalizers.fit("linear", data)
        out = normalizers.apply(st, data)
        assert out.min() == -1.0 and out.max() == 1.0

    def test_range(self):
        st = normalizers.fit("range", np.zeros((1, 1)), scale=255.0, shift=-0.5)
        out = normalizers.apply(st, np.array([[255.0]]))
        np.testing.assert_allclose(out, [[0.5]])

    def test_external_mean(self):
        st = normalizers.fit(
            "external_mean", np.zeros((1, 2)), mean=np.array([1.0, 2.0])
        )
        np.testing.assert_allclose(
            normalizers.apply(st, np.array([[1.0, 2.0]])), [[0.0, 0.0]]
        )


class TestDatasets:
    def test_mnist_synthetic_shapes(self):
        ld = datasets.mnist(n_train=50, n_test=20, minibatch_size=25)
        assert ld.class_lengths == {"train": 50, "test": 20}
        mb = next(iter(ld.batches("train")))
        assert mb.data.shape == (25, 784)
        assert mb.labels.min() >= 0 and mb.labels.max() < 10

    def test_mnist_conv_layout(self):
        ld = datasets.mnist(n_train=10, n_test=4, flat=False, minibatch_size=10)
        mb = next(iter(ld.batches("train")))
        assert mb.data.shape == (10, 28, 28, 1)

    def test_mnist_validation_split(self):
        ld = datasets.mnist(n_train=100, n_test=10, validation_ratio=0.2)
        assert ld.class_lengths["valid"] == 20
        assert ld.class_lengths["train"] == 80

    def test_cifar_synthetic(self):
        ld = datasets.cifar10(n_train=20, n_test=8, minibatch_size=10)
        mb = next(iter(ld.batches("train")))
        assert mb.data.shape == (10, 32, 32, 3)

    def test_wine(self):
        ld = datasets.wine()
        assert ld.class_lengths["train"] == 178
        mb = next(iter(ld.batches("train")))
        assert mb.data.shape == (10, 13)

    def test_determinism_under_seed(self):
        prng.seed_all(42)
        a = datasets.mnist(n_train=10, n_test=5)
        prng.seed_all(42)
        b = datasets.mnist(n_train=10, n_test=5)
        np.testing.assert_array_equal(a.data["train"], b.data["train"])


class TestReviewRegressions:
    def test_partial_mnist_dir_raises(self, tmp_path):
        # only a labels file present -> must not silently mix real/synthetic
        import gzip
        import struct

        lab = tmp_path / "t10k-labels-idx1-ubyte.gz"
        with gzip.open(lab, "wb") as f:
            f.write(struct.pack(">ii", 0x00000801, 2) + bytes([1, 2]))
        im = tmp_path / "t10k-images-idx3-ubyte.gz"
        with gzip.open(im, "wb") as f:
            f.write(
                struct.pack(">iiii", 0x00000803, 2, 2, 2) + bytes(8)
            )
        import pytest

        with pytest.raises(FileNotFoundError):
            datasets.mnist(str(tmp_path))

    def test_normalizer_without_train_split_raises(self):
        import pytest

        with pytest.raises(ValueError):
            FullBatchLoader(
                {"valid": np.zeros((4, 2), np.float32)}, normalization="linear"
            )

    def test_resume_reproduces_shuffle_stream(self):
        prng.seed_all(5)
        ld = _loader(25, 25)
        list(ld.batches("train"))
        state = ld.state_dict()
        later = [next(iter(ld.batches("train"))).indices for _ in range(3)]
        # "restart the process": fresh prng registry, different seed history
        prng.reset()
        prng.seed_all(999)
        ld2 = _loader(25, 25)
        ld2.load_state_dict(state)
        resumed = [next(iter(ld2.batches("train"))).indices for _ in range(3)]
        for a, b in zip(later, resumed):
            np.testing.assert_array_equal(a, b)


class TestBalancedShuffle:
    def test_every_batch_has_proportional_mix(self):
        # 90/10 imbalance: with balanced=True each size-10 batch holds ~1
        # minority sample instead of clumping
        prng.seed_all(3)
        x = np.zeros((100, 4), np.float32)
        y = np.array([0] * 90 + [1] * 10, np.int32)
        ld = FullBatchLoader(
            {"train": x}, {"train": y}, minibatch_size=10, balanced=True
        )
        for mb in ld.batches("train"):
            minority = int((mb.labels[mb.mask > 0] == 1).sum())
            assert minority in (0, 1, 2)  # near-proportional, never clumped
        # all samples still served exactly once
        seen = np.concatenate(
            [mb.indices[mb.mask > 0] for mb in ld.batches("train")]
        )
        assert sorted(seen.tolist()) == list(range(100))

    def test_unbalanced_default_unchanged(self):
        prng.seed_all(3)
        x = np.zeros((20, 2), np.float32)
        ld = FullBatchLoader({"train": x}, minibatch_size=5)
        assert ld.balanced is False
        list(ld.batches("train"))


class TestImageDirectoryLoader:
    def _make_tree(self, tmp_path, n_per_class=4, classes=("cat", "dog")):
        import matplotlib

        matplotlib.use("Agg", force=False)
        import matplotlib.image as mpimg

        rng = np.random.default_rng(0)
        for split, n in (("train", n_per_class), ("test", 2)):
            for ci, cls in enumerate(classes):
                d = tmp_path / split / cls
                d.mkdir(parents=True, exist_ok=True)
                for i in range(n):
                    img = rng.random((8, 8, 3)).astype(np.float32)
                    img[:, :, ci % 3] = 1.0  # class-correlated channel
                    mpimg.imsave(str(d / f"{i}.png"), img)
        return tmp_path

    def test_loads_and_labels(self, tmp_path):
        from znicz_tpu.loader.image import ImageDirectoryLoader

        root_dir = self._make_tree(tmp_path)
        ld = ImageDirectoryLoader(str(root_dir), minibatch_size=4)
        assert ld.class_lengths == {"train": 8, "test": 4}
        assert ld.classes == ["cat", "dog"]
        assert ld.sample_shape == (8, 8, 3)
        mb = next(iter(ld.batches("train")))
        assert mb.data.shape == (4, 8, 8, 3)
        assert mb.data.max() <= 1.0
        # labels come from directory names
        seen = set()
        for b in ld.batches("train"):
            seen.update(b.labels[b.mask > 0].tolist())
        assert seen == {0, 1}

    def test_resize_and_grayscale(self, tmp_path):
        from znicz_tpu.loader.image import ImageDirectoryLoader

        root_dir = self._make_tree(tmp_path)
        ld = ImageDirectoryLoader(
            str(root_dir),
            target_shape=(4, 4),
            grayscale=True,
            minibatch_size=4,
        )
        mb = next(iter(ld.batches("train")))
        assert mb.data.shape == (4, 4, 4, 1)

    def test_missing_dir_raises(self, tmp_path):
        from znicz_tpu.loader.image import ImageDirectoryLoader

        with pytest.raises(FileNotFoundError):
            ImageDirectoryLoader(str(tmp_path / "nope"))

    def test_balanced_uses_directory_labels(self, tmp_path):
        from znicz_tpu.loader.image import ImageDirectoryLoader

        root_dir = self._make_tree(tmp_path, n_per_class=8)
        ld = ImageDirectoryLoader(
            str(root_dir), minibatch_size=4, balanced=True
        )
        labels = ld.split_labels("train")
        assert sorted(labels.tolist()) == [0] * 8 + [1] * 8
        for mb in ld.batches("train"):
            valid = mb.labels[mb.mask > 0]
            assert set(valid.tolist()) == {0, 1}  # every batch mixed

    def test_empty_class_dir_ignored(self, tmp_path):
        from znicz_tpu.loader.image import ImageDirectoryLoader

        root_dir = self._make_tree(tmp_path)
        (root_dir / "train" / "phantom").mkdir()
        (root_dir / "train" / "phantom" / "notes.txt").write_text("x")
        ld = ImageDirectoryLoader(str(root_dir), minibatch_size=4)
        assert ld.classes == ["cat", "dog"]

    def test_grayscale_inferred_shape(self, tmp_path):
        from znicz_tpu.loader.image import ImageDirectoryLoader

        root_dir = self._make_tree(tmp_path)
        ld = ImageDirectoryLoader(
            str(root_dir), grayscale=True, minibatch_size=4
        )
        # inferred target must honor grayscale, and averaging (not
        # red-channel slicing) must be used: cat images have red=1.0
        assert ld.sample_shape == (8, 8, 1)
        mb = next(iter(ld.batches("train")))
        assert float(mb.data.max()) < 1.0  # mean of (1, r, r) < 1

    def test_trains_in_workflow(self, tmp_path):
        from znicz_tpu.loader.image import ImageDirectoryLoader
        from znicz_tpu.workflow import StandardWorkflow

        root_dir = self._make_tree(tmp_path, n_per_class=8)
        ld = ImageDirectoryLoader(str(root_dir), minibatch_size=8)
        wf = StandardWorkflow(
            ld,
            [
                {"type": "all2all_tanh", "->": {"output_sample_shape": 8}},
                {"type": "softmax", "->": {"output_sample_shape": 2}},
            ],
            decision_config={"max_epochs": 8},
            default_hyper={"learning_rate": 0.2, "gradient_moment": 0.9},
        )
        wf.initialize(seed=3)
        dec = wf.run()
        assert dec.history[-1]["train"]["n_err"] == 0  # separable by channel


class TestPrefetch:
    def test_order_preserved(self):
        from znicz_tpu.loader.prefetch import prefetch

        assert list(prefetch(iter(range(100)), depth=4)) == list(range(100))

    def test_abandoned_iterator_stops_worker(self):
        import threading
        import time

        from znicz_tpu.loader.prefetch import prefetch

        before = threading.active_count()
        it = prefetch(iter(range(1000)), depth=2)
        next(it)
        it.close()  # abandon mid-stream with a full queue
        time.sleep(0.5)
        assert threading.active_count() <= before + 1  # worker exited

    def test_producer_exception_propagates(self):
        from znicz_tpu.loader.prefetch import prefetch

        def gen():
            yield 1
            raise RuntimeError("decode failed")

        it = prefetch(gen(), depth=2)
        assert next(it) == 1
        with pytest.raises(RuntimeError, match="decode failed"):
            list(it)

    def test_an_epoch_end_passes_through_and_restarts_the_wait_labels(self):
        from znicz_tpu.loader.prefetch import EpochEnd, prefetch
        from znicz_tpu.observability import get_registry, pipeline

        def two_epochs():
            yield from (1, 2, 3)
            yield EpochEnd("a")
            yield from (4, 5)
            yield EpochEnd("b")

        pipeline.reset_window()
        out = list(prefetch(two_epochs(), depth=2, transform=lambda v: -v))
        # the marker is handed over as it is, in its place
        assert [v.state if isinstance(v, EpochEnd) else v for v in out] == [
            -1, -2, -3, "a", -4, -5, "b",
        ]
        fams = get_registry().metrics()
        waits = {
            k[0]: c.count
            for k, c in fams[pipeline.WAIT_METRIC].children().items()
        }
        # two markers and the iterable's own end; a first after each start
        assert waits == {"first": 2, "steady": 3, "end": 3}
        starts = {
            k[0]: c.value
            for k, c in fams[pipeline.PREFETCH_EPOCHS_METRIC]
            .children().items()
        }
        assert starts == {"cold": 1, "carried": 1}

    def test_carried_epochs_park_puts_the_loader_back_to_the_boundary(self):
        import pickle
        import threading

        from znicz_tpu.loader.prefetch import CarriedEpochs, THREAD_NAME

        def loader():
            prng.reset()
            prng.seed_all(9)
            return datasets.mnist(n_train=96, n_test=32, minibatch_size=32)

        plain = loader()
        want, states = [], []
        for _ in range(2):
            want.append([(s, mb.indices.copy()) for s, mb in plain.epoch()])
            states.append(pickle.dumps(plain.state_dict()))

        carried = loader()
        before = {t for t in threading.enumerate() if t.name == THREAD_NAME}
        feed = CarriedEpochs(carried, 2, lambda item: item)
        assert pickle.dumps(feed.boundary[0]) == pickle.dumps(
            loader().state_dict()
        )
        for epoch in range(2):
            got = [(s, mb.indices.copy()) for s, mb in feed.epoch()]
            assert [s for s, _ in got] == [s for s, _ in want[epoch]]
            for (_, a), (_, b) in zip(got, want[epoch]):
                np.testing.assert_array_equal(a, b)
            # the producer's copy from the boundary, not the loader's now
            assert pickle.dumps(feed.boundary[0]) == states[epoch]
        feed.park()
        assert not {
            t for t in threading.enumerate()
            if t.name == THREAD_NAME and t.is_alive()
        } - before
        assert pickle.dumps(carried.state_dict()) == states[-1]
        # a parked feed is over: its next epoch says so
        with pytest.raises(Exception, match="parked"):
            list(feed.epoch())

    def test_workflow_results_identical_with_and_without(self):
        from znicz_tpu.workflow import StandardWorkflow

        def run(prefetch_batches):
            prng.seed_all(55)
            loader = datasets.mnist(n_train=128, n_test=32, minibatch_size=32)
            wf = StandardWorkflow(
                loader,
                [
                    {"type": "all2all_tanh", "->": {"output_sample_shape": 8}},
                    {"type": "softmax", "->": {"output_sample_shape": 10}},
                ],
                decision_config={"max_epochs": 2},
                default_hyper={"learning_rate": 0.1},
                prefetch_batches=prefetch_batches,
            )
            wf.initialize(seed=55)
            return wf.run().history

        # identical losses: prefetch must not change draw order or batching
        a = run(0)
        b = run(2)
        for ea, eb in zip(a, b):
            assert ea["train"]["loss"] == eb["train"]["loss"]


def test_split_sizes():
    s = split_sizes(100, [0.1, 0.2])
    assert s == {"train": 70, "valid": 10, "test": 20}
