"""The input path accounts for its own time (PR 24).

The producer's stages tile its loop (their sum is the producer's own
total, with or without a fault armed inside a stage; time outside every
stage shows as ``producer_unattributed_frac`` and lowers the verdict's
confidence), waits are labelled by where in the epoch they fall, a
copy's landing time is taken beside the loop without delaying the
hand-off, and spans reach the profiler's annotations with the tracer
idle.
"""

import gc
import json
import os
import pickle
import sys
import threading
import time
from contextlib import nullcontext

import numpy as np
import pytest

from test_flight_recorder import MLP, _stream_workflow
from znicz_tpu.loader import prefetch as prefetch_mod
from znicz_tpu.loader.prefetch import prefetch
from znicz_tpu.observability import (
    MetricsRegistry,
    PipelineAttribution,
    get_registry,
    get_tracer,
    pipeline,
)
from znicz_tpu.observability.tracing import Tracer
from znicz_tpu.utils import faults


def _stage_sums(registry=None):
    reg = registry if registry is not None else get_registry()
    stage = reg.metrics()[pipeline.STAGE_METRIC]
    return {k[0]: (c.count, c.sum) for k, c in stage.children().items()}


def _producer(registry=None):
    reg = registry if registry is not None else get_registry()
    child = reg.metrics()[pipeline.PRODUCER_METRIC].children()[()]
    return child.count, child.sum


class _Annotation:
    """Stands in for ``jax.profiler.TraceAnnotation``: keeps the names
    entered, from whichever thread."""

    entered = []
    _lock = threading.Lock()

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        with self._lock:
            type(self).entered.append(self.name)
        return self

    def __exit__(self, *exc):
        return False


@pytest.fixture
def annotations(monkeypatch):
    _Annotation.entered = []
    monkeypatch.setattr(get_tracer(), "_annotation", _Annotation)
    return _Annotation.entered


class TestProducerStagesTileItsLoop:
    @pytest.mark.parametrize("fault", [None, "loader.fetch", "loader.h2d"])
    def test_stages_sum_to_the_producer_total(self, fault):
        wf = _stream_workflow()
        wf.run_epoch()  # compile
        # the producer goes on past an epoch's end: the window opens and
        # closes on a parked one, and holds a boundary it ran across
        wf.sync_epoch()
        pipeline.reset_window()
        epochs = 2
        with faults.injected(fault, delay=0.01) if fault else nullcontext():
            for _ in range(epochs):
                wf.run_epoch()
            wf.sync_epoch()
        sums = _stage_sums()
        count, total = _producer()
        steps = 512 // 64
        # an epoch's end is an iteration too (the marker's fetch and its
        # hand-over); the last ones are the run-ahead the park dropped
        # (the queue's two, the one in hand, the fetch it was stopped in)
        consumed = epochs * (steps + 1)
        assert consumed <= count <= consumed + 4
        tiled = sum(sums.get(s, (0, 0.0))[1] for s in pipeline.TILING_STAGES)
        assert tiled == pytest.approx(total, rel=0.02)
        assert sums[pipeline.STAGE_FETCH][0] == count
        assert epochs * steps <= sums[pipeline.STAGE_H2D][0] <= count - epochs
        assert consumed <= sums[pipeline.STAGE_ENQUEUE][0] <= count
        if fault is not None:
            # the armed delay is inside the stage it is named after
            stage = fault.split(".")[1]
            assert sums[stage][1] >= epochs * steps * 0.01
        att = PipelineAttribution.from_registry().attribution()
        assert att["producer_unattributed_frac"] < 0.02
        assert att["producer_seconds"] == pytest.approx(total, abs=1e-5)

    @pytest.mark.parametrize("observed", [True, False])
    def test_time_outside_every_stage_is_shown_and_lowers_confidence(
        self, observed
    ):
        def slow(item):
            time.sleep(0.005)
            return item

        pipeline.reset_window()
        wall = pipeline.step_wall_seconds()
        t_prev = time.perf_counter()
        # transform_stage=None: the callable owns its instrumentation,
        # and this one observes nothing, so its 5 ms are in no stage
        for _ in prefetch(
            iter(range(30)), depth=2, transform=slow,
            transform_stage=pipeline.STAGE_TRANSFORM if observed else None,
        ):
            now = time.perf_counter()
            wall.observe(now - t_prev)
            t_prev = now
        att = PipelineAttribution.from_registry().attribution()
        assert att["verdict"] == "input-bound"
        if observed:
            assert att["producer_unattributed_frac"] < 0.02
            assert att["confidence"] == "high"
        else:
            assert att["producer_unattributed_frac"] > 0.8
            assert att["confidence"] == "low"
            assert att["stages"][pipeline.STAGE_ENQUEUE] < 0.05

    def test_a_capture_without_the_producer_total_claims_no_hole(self):
        reg = MetricsRegistry()
        pipeline.step_wall_seconds(reg).observe(1.0)
        pipeline.stage_seconds(reg).labels(stage="fetch").observe(0.5)
        att = PipelineAttribution.from_registry(reg).attribution()
        assert att["producer_unattributed_frac"] == 0.0
        assert att["producer_seconds"] == 0.5


class TestWaitsByPosition:
    def test_one_first_one_end_and_the_rest_steady_per_epoch(self):
        wf = _stream_workflow()
        wf.run_epoch()
        pipeline.reset_window()
        epochs, steps = 2, 512 // 64
        for _ in range(epochs):
            wf.run_epoch()
        waits = get_registry().metrics()[pipeline.WAIT_METRIC].children()
        counts = {k[0]: c.count for k, c in waits.items()}
        assert counts == {
            pipeline.WAIT_FIRST: epochs,
            pipeline.WAIT_STEADY: epochs * (steps - 1),
            pipeline.WAIT_END: epochs,
        }
        att = PipelineAttribution.from_registry().attribution()
        by_at = sum(att["waits"].values())
        whole = sum(c.sum for c in waits.values())
        assert by_at == pytest.approx(whole, abs=1e-5)


def _producers():
    return {
        t for t in threading.enumerate()
        if t.name == prefetch_mod.THREAD_NAME and t.is_alive()
    }


def _gone(threads, timeout=5.0):
    """True once none of ``threads`` is alive (a parked producer has
    been joined; a dropped one ends at its next look at the stop flag)."""
    deadline = time.perf_counter() + timeout
    while any(t.is_alive() for t in threads):
        if time.perf_counter() > deadline:
            return False
        time.sleep(0.005)
    return True


def _crop_workflow(root, prefetch_batches, **kw):
    """A stepwise workflow over a packed image file: every train batch
    draws its crop offsets and flips from the loader's stream, after
    the epoch's shuffle.  Fed batches are kept in ``wf.fed``, the crop
    parameters as drawn in ``wf.drawn``."""
    import jax

    from znicz_tpu.core import prng
    from znicz_tpu.loader import ImageNetLoader
    from znicz_tpu.workflow import StandardWorkflow

    os.makedirs(root, exist_ok=True)
    gen = np.random.default_rng(5)
    for split, n in (("train", 48), ("valid", 16)):
        np.save(
            os.path.join(root, f"{split}_images.npy"),
            gen.integers(0, 256, (n, 12, 12, 3), dtype=np.uint8),
        )
        np.save(
            os.path.join(root, f"{split}_labels.npy"),
            gen.integers(0, 10, n).astype(np.int32),
        )
    prng.reset()
    prng.seed_all(11)
    loader = ImageNetLoader(root, crop_size=8, minibatch_size=16)
    kw.setdefault("decision_config", {"max_epochs": 10000})
    wf = StandardWorkflow(
        loader, MLP,
        default_hyper={"learning_rate": 0.1, "gradient_moment": 0.9},
        epoch_dispatch="step", prefetch_batches=prefetch_batches, **kw,
    )
    wf.initialize(seed=11)
    wf.fed, wf.drawn = [], []
    crop_params = loader._crop_params

    def drawing(indices, split):
        out = crop_params(indices, split)
        wf.drawn.append((split, np.array(indices), *out))
        return out

    loader._crop_params = drawing
    for name in ("_train_step", "_eval_step"):
        step = getattr(wf, name)

        def fed(*args, _step=step):
            wf.fed.append(jax.device_get(args[1:4]))  # x, y, mask
            return _step(*args)

        setattr(wf, name, fed)
    return wf


def _same(a, b):
    """Equal as a snapshot would hold them: byte for byte."""
    return pickle.dumps(a) == pickle.dumps(b)


def _first_waits():
    child = get_registry().metrics()[pipeline.WAIT_METRIC].children()
    return child[(pipeline.WAIT_FIRST,)].sum


class TestTheProducerOutlivesTheEpoch:
    """PR 29: one producer for as long as ``run_epoch()`` keeps being
    called.  The work is the parent's (same draws, same order, same
    state at every boundary); only when it happens differs."""

    STEPS = 48 // 16 + 16 // 16  # train + valid batches an epoch

    def test_three_epochs_feed_and_draw_what_no_prefetch_does(self, tmp_path):
        runs = {}
        for depth in (0, 2):
            wf = _crop_workflow(str(tmp_path / "packed"), depth)
            states = []
            for _ in range(3):
                wf.run_epoch()
                states.append(wf.host_state())
            if depth:
                # the loader itself goes on into the next epoch: the
                # state above is the producer's copy from the boundary
                deadline = time.perf_counter() + 5.0
                while _same(
                    wf.loader._order, states[-1]["loader"]["order"]
                ):
                    assert time.perf_counter() < deadline
                    time.sleep(0.005)
            wf.sync_epoch()
            runs[depth] = (wf.fed, wf.drawn, states, wf.host_state())
        fed0, drawn0, states0, parked0 = runs[0]
        fed2, drawn2, states2, parked2 = runs[2]
        assert len(fed0) == len(fed2) == 3 * self.STEPS
        for a, b in zip(fed0, fed2):
            for u, v in zip(a, b):
                np.testing.assert_array_equal(u, v)
        # indices, offsets and flips as drawn; the carried producer has
        # drawn on into a fourth epoch that nobody ran
        assert len(drawn0) == 3 * self.STEPS <= len(drawn2)
        for a, b in zip(drawn0, drawn2):
            assert a[0] == b[0]
            for u, v in zip(a[1:], b[1:]):
                np.testing.assert_array_equal(u, v)
        for a, b in zip(states0, states2):
            assert _same(a, b)
        assert _same(parked0, parked2)  # and the park put it all back

    def test_a_snapshot_and_a_resume_are_byte_identical(self, tmp_path):
        def train(depth, where, epochs, snapshot=None):
            wf = _crop_workflow(
                str(tmp_path / "packed"), depth, snapshot_dir=str(where),
                snapshot_config={"interval": 1, "compress": False},
            )
            if snapshot:
                wf.initialize(snapshot=str(snapshot))
            for _ in range(epochs):
                wf.run_epoch()
            wf.sync_epoch()
            return wf

        name = "StandardWorkflow_epoch{}.pickle".format
        for depth, where in ((0, "plain"), (2, "carried")):
            train(depth, tmp_path / where, 2)
            # resumed from its own file, behind a producer again or not
            # (a resumed run pickles its loaded history's strings apart,
            # so it is held against a resumed run)
            train(
                depth, tmp_path / f"{where}_resumed", 1,
                tmp_path / where / name(1),
            )
        for where, epoch in (
            ("plain", 0), ("plain", 1), ("plain_resumed", 2),
        ):
            carried = where.replace("plain", "carried")
            assert (tmp_path / where / name(epoch)).read_bytes() == (
                tmp_path / carried / name(epoch)
            ).read_bytes()

    @pytest.mark.parametrize(
        "leave", ["decision_stop", "sync_epoch", "rollback", "preemption"]
    )
    def test_leaving_the_loop_parks_the_producer(self, leave, tmp_path):
        from znicz_tpu.core import prng
        from znicz_tpu.workflow.recovery import (
            RecoveryPolicy,
            TrainingPreempted,
        )

        def run(depth):
            kw = {}
            if leave == "decision_stop":
                kw["decision_config"] = {"max_epochs": 2}
            if leave == "rollback":
                kw["recovery"] = RecoveryPolicy(
                    max_rollbacks=2, lr_backoff=1.0, perturb=False
                )
            wf = _crop_workflow(str(tmp_path / "packed"), depth, **kw)
            if leave == "decision_stop":
                wf.run()
            elif leave == "sync_epoch":
                wf.run_epoch()
                wf.run_epoch()
                wf.sync_epoch()
            elif leave == "rollback":
                wf.run_epoch()
                # the second epoch's second step reads NaN: back to
                # that epoch's start
                with faults.injected(
                    "train.step_nan", flag=True, times=1, after=1
                ):
                    assert wf.run_epoch() is None
                assert wf.recovery.rollbacks_used == 1
            else:
                wf.run_epoch()
                wf.request_stop()
                with pytest.raises(TrainingPreempted):
                    wf.run_epoch()
            return wf.loader.state_dict(), prng.state_dict()

        gc.collect()
        before = _producers()
        plain = run(0)
        assert _producers() == before
        carried = run(2)
        assert _gone(_producers() - before)
        assert _same(plain, carried)

    def test_a_dropped_workflow_ends_its_producer(self):
        gc.collect()
        before = _producers()
        wf = _stream_workflow()
        wf.run_epoch()
        mine = _producers() - before
        assert len(mine) == 1
        del wf  # no park: the thread holds nothing that holds the workflow
        gc.collect()
        assert _gone(mine)

    def test_the_next_epochs_first_batch_is_fetched_behind_the_loop(self):
        wf = _stream_workflow()
        wf.run_epoch()  # compile
        wf.sync_epoch()
        pipeline.reset_window()
        delay, epochs = 0.05, 3
        firsts = []
        with faults.injected("loader.fetch", delay=delay):
            for _ in range(epochs):
                before = _first_waits() if firsts else 0.0
                wf.run_epoch()
                firsts.append(_first_waits() - before)
                # what the loop does between epochs (on the chip: the
                # wait for the last step's metrics); the producer works
                time.sleep(4 * delay)
            wf.sync_epoch()
        # started for the epoch: a whole fetch (less the moment between
        # the thread's start and the consumer's clock read)
        assert firsts[0] >= 0.8 * delay
        assert max(firsts[1:]) < delay / 5  # went on into it: queued
        starts = get_registry().metrics()[
            pipeline.PREFETCH_EPOCHS_METRIC
        ].children()
        assert {k[0]: c.value for k, c in starts.items()} == {
            pipeline.START_COLD: 1, pipeline.START_CARRIED: epochs - 1,
        }

    def test_the_stages_tile_the_loop_across_boundaries(self):
        wf = _stream_workflow()
        wf.run_epoch()
        wf.sync_epoch()
        pipeline.reset_window()
        for _ in range(3):
            wf.run_epoch()
        wf.sync_epoch()
        att = PipelineAttribution.from_registry().attribution()
        assert att["producer_unattributed_frac"] < 0.01
        count, total = _producer()
        assert count >= 3 * (512 // 64 + 1)
        assert att["producer_seconds"] == pytest.approx(total, abs=1e-5)

    def test_a_producers_error_parks_it_and_the_next_epoch_starts_cold(self):
        from znicz_tpu.loader.base import LoaderFetchError

        gc.collect()
        before = _producers()
        wf = _stream_workflow()
        wf.loader.fetch_retries = 0
        wf.run_epoch()
        state = wf.host_state()
        with faults.injected("loader.fetch_flaky", times=1):
            with pytest.raises(LoaderFetchError):
                wf.run_epoch()
        assert wf._feed is None and _gone(_producers() - before)
        # back at the boundary the failed epoch started from
        assert _same(state["loader"], wf.loader.state_dict())
        assert wf.run_epoch()["summary"]["train"]["n_samples"] == 512


class _Landing:
    """A device array's stand-in whose copy takes ``seconds`` to land."""

    def __init__(self, seconds):
        self.seconds = seconds

    def block_until_ready(self):
        time.sleep(self.seconds)
        return self


class TestLandingTime:
    def test_landed_is_never_under_the_call_time(self):
        reg = MetricsRegistry()
        probe = pipeline.H2DProbe(reg)
        was = {"h2d": 0.0, "h2d_landed": 0.0}
        for i in range(8):
            with probe.measure(1000) as transfer:
                transfer.watch(_Landing(0.0), None)
            assert probe.drain()
            sums = _stage_sums(reg)
            call = sums["h2d"][1] - was["h2d"]
            landed = sums["h2d_landed"][1] - was["h2d_landed"]
            assert landed >= call > 0
            assert sums["h2d"][0] == sums["h2d_landed"][0] == i + 1
            was = {k: sums[k][1] for k in was}

    def test_a_slow_landing_does_not_delay_the_hand_off(self):
        reg = MetricsRegistry()
        probe = pipeline.H2DProbe(reg)
        slow, n = 0.05, 8

        def place(item):
            with probe.measure(1_000_000) as transfer:
                transfer.watch(_Landing(slow))
            return item

        t0 = time.perf_counter()
        out = list(
            prefetch(iter(range(n)), depth=2, transform=place,
                     transform_stage=None)
        )
        epoch = time.perf_counter() - t0
        assert out == list(range(n))
        # the epoch does not wait for one landing, let alone eight
        assert epoch < slow
        assert probe.drain()
        sums = _stage_sums(reg)
        assert sums["h2d_landed"][0] == n
        assert sums["h2d_landed"][1] >= n * slow
        assert sums["h2d"][1] < slow
        # the live gauge and the attribution's headline divide by
        # landed seconds: at most 1 MB / 50 ms
        rate = reg.metrics()[pipeline.H2D_BPS_METRIC].value
        assert 0 < rate <= 1_000_000 / slow
        att = PipelineAttribution.from_registry(reg).attribution()
        assert att["h2d_bytes_per_second"] == pytest.approx(
            n * 1_000_000 / sums["h2d_landed"][1], rel=1e-3
        )

    def test_the_workflow_hands_every_batch_to_the_watcher(self):
        wf = _stream_workflow()
        wf.run_epoch()
        wf.sync_epoch()  # a parked producer places nothing behind the reset
        assert wf._h2d_probe.drain()
        pipeline.reset_window()
        wf.run_epoch()
        wf.sync_epoch()
        assert wf._h2d_probe.drain()
        sums = _stage_sums()
        # the epoch's batches and the few of the next it ran ahead into
        assert sums["h2d_landed"][0] == sums["h2d"][0] >= 512 // 64
        assert sums["h2d_landed"][1] >= sums["h2d"][1]


class TestSpansWithoutARecordingTracer:
    def test_an_idle_span_reaches_the_annotation_and_emits_no_event(self):
        tracer = Tracer()
        _Annotation.entered = []
        tracer._annotation = _Annotation
        assert not tracer.recording
        with tracer.span("outer", request=1):
            with tracer.span("inner"):
                pass
        assert _Annotation.entered == ["outer", "inner"]
        assert tracer.events() == []
        # recording: the same annotation, and now the Chrome events too
        tracer.start()
        with tracer.span("recorded"):
            pass
        events = tracer.stop()
        assert _Annotation.entered[-1] == "recorded"
        assert [e["name"] for e in events] == ["recorded"]

    def test_no_annotation_before_jax_is_imported(self, monkeypatch):
        tracer = Tracer()
        monkeypatch.delitem(sys.modules, "jax")
        assert tracer._annotation_cls() is None
        with tracer.span("jax-free"):
            pass
        monkeypatch.undo()
        import jax

        assert tracer._annotation_cls() is jax.profiler.TraceAnnotation

    def test_an_epoch_is_annotated_on_every_thread_with_the_tracer_idle(
        self, annotations
    ):
        wf = _stream_workflow()
        assert not get_tracer().recording
        # what an earlier file on this worker left in the process-wide
        # tracer is not this test's: the epoch must add nothing to it
        before = get_tracer().events()
        wf.run_epoch()
        assert wf._h2d_probe.drain()
        names = set(annotations)
        assert {
            "train/dispatch/train", "loader/fetch", "loader/h2d",
            "loader/h2d_landed",
        } <= names
        assert get_tracer().events() == before

    def test_a_profiler_session_holds_the_spans_with_the_tracer_idle(
        self, tmp_path
    ):
        # no stub: utils.profiling.trace around one epoch, then the
        # profiler's own file (the CPU backend has a host plane too)
        from jax.profiler import ProfileData

        from znicz_tpu.utils import profiling

        wf = _stream_workflow()
        wf.run_epoch()
        assert not get_tracer().recording
        before = get_tracer().events()
        with profiling.trace(str(tmp_path)):
            wf.run_epoch()
            assert wf._h2d_probe.drain()
        (trace_file,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
        names = {
            e.name
            for plane in ProfileData.from_file(str(trace_file)).planes
            if plane.name == "/host:CPU"
            for line in plane.lines
            for e in line.events
            if e.name.startswith(("train/", "loader/"))
        }
        assert {
            "train/dispatch/train", "loader/fetch", "loader/h2d",
            "loader/h2d_landed",
        } <= names
        assert get_tracer().events() == before

    def test_the_imagenet_loader_names_the_parts_of_its_fetch(
        self, annotations, tmp_path
    ):
        import numpy as np

        from znicz_tpu.loader import ImageNetLoader

        np.save(
            tmp_path / "train_images.npy",
            np.zeros((16, 12, 12, 3), np.uint8),
        )
        np.save(tmp_path / "train_labels.npy", np.zeros(16, np.int32))
        pipeline.reset_window()
        for resident, parts in (
            (False, {"crop_params", "crop"}), (True, {"crop_params"}),
        ):
            loader = ImageNetLoader(
                str(tmp_path), crop_size=8, pack_size=12, minibatch_size=8,
                device_resident=resident,
            )
            annotations.clear()
            pipeline.reset_window()
            loader.fill(np.arange(8), "train")
            assert set(annotations) == {f"loader/{p}" for p in parts}
            assert {
                k for k, (count, _) in _stage_sums().items() if count
            } == parts


class TestServingCounters:
    def test_span_arguments_are_built_only_for_a_recording_tracer(self):
        from znicz_tpu.services.engine import PagedDecodeEngine

        class Resident:
            trace_id = "t-1"

        engine = PagedDecodeEngine.__new__(PagedDecodeEngine)
        engine.trace_instance = "replica-0"
        assert not get_tracer().recording
        assert engine._trace_args("t-1") == {}
        assert engine._decode_trace_args([Resident()]) == {}
        get_tracer().start()
        try:
            assert engine._trace_args("t-1") == {
                "trace": "t-1", "instance": "replica-0",
            }
            assert engine._decode_trace_args([Resident()]) == {
                "traces": "t-1", "instance": "replica-0",
            }
        finally:
            get_tracer().stop()

    def test_queue_waits_once_per_request_and_the_gather_as_gathered(self):
        import numpy as np

        from znicz_tpu import observability as obs
        from znicz_tpu.core import prng
        from znicz_tpu.services import PagedDecodeEngine, ServingFrontDoor
        from znicz_tpu.workflow.transformer import init_lm_params

        def read():
            fams = obs.get_registry().metrics()

            def hist(name):
                m = fams.get(name)
                return sum(c.count for c in m.children().values()) if m else 0

            def value(name):
                m = fams.get(name)
                return sum(c.value for c in m.children().values()) if m else 0.0

            chunks = fams.get("znicz_serve_decode_chunks_total")
            return {
                "door": hist("znicz_serve_frontdoor_queue_wait_seconds"),
                "engine": hist("znicz_serve_engine_queue_wait_seconds"),
                "steps": value("znicz_serve_decode_steps_total"),
                "gathered": value("znicz_serve_decode_gathered_tokens_total"),
                "by_window": {
                    int(k[0]): c.value for k, c in chunks.children().items()
                } if chunks else {},
                "decode_phases": sum(
                    c.count
                    for k, c in fams["znicz_serve_phase_seconds"]
                    .children().items() if k[0] == "decode"
                ) if "znicz_serve_phase_seconds" in fams else 0,
            }

        prng.seed_all(27)
        params = init_lm_params(17, 32, 2, 4, max_seq=64)
        slots, block, chunk = 2, 8, 4

        def factory():
            return PagedDecodeEngine(
                params, n_heads=4, eos_id=14, batch_size=slots,
                block_size=block, max_seq=64, admit_every=chunk,
            )

        gen = np.random.default_rng(7)
        prompts = [
            gen.integers(0, 14, (k,)).astype(np.int32) for k in (5, 12, 3)
        ]
        before = read()
        with ServingFrontDoor(factory) as door:
            handles = [door.submit(p, 6) for p in prompts]
            for h in handles:
                assert h.result(timeout=60.0).finish_reason in (
                    "eos", "budget"
                )
        after = read()
        n = len(prompts)
        assert after["door"] - before["door"] == n
        assert after["engine"] - before["engine"] == n
        chunks = {
            w: after["by_window"][w] - before["by_window"].get(w, 0.0)
            for w in after["by_window"]
        }
        n_chunks = sum(chunks.values())
        assert n_chunks == after["decode_phases"] - before["decode_phases"]
        steps = after["steps"] - before["steps"]
        assert 0 < steps <= n_chunks * chunk
        gathered = after["gathered"] - before["gathered"]
        # every slot's window of positions per step, active or not:
        # windows are x2 rungs of blocks, here 1 or 2 of 8 positions
        assert set(w for w, c in chunks.items() if c) <= {1, 2, 4, 8}
        assert steps * slots * block <= gathered <= steps * slots * 8 * block
        if len([w for w, c in chunks.items() if c]) == 1:
            (window,) = [w for w, c in chunks.items() if c]
            assert gathered == steps * slots * window * block


class TestDoctorPrintsTheProducerTable:
    def test_stage_table_and_waits_from_an_exposition(self, tmp_path, capsys):
        from znicz_tpu.observability import doctor

        wf = _stream_workflow(n=256, bs=32)
        wf.run_epoch()
        wf.sync_epoch()
        assert wf._h2d_probe.drain()
        pipeline.reset_window()
        wf.run_epoch()
        wf.sync_epoch()  # the capture is read twice: nothing may move
        assert wf._h2d_probe.drain()
        prom = tmp_path / "metrics.prom"
        prom.write_text(get_registry().prometheus_text())
        assert doctor.main([str(prom)]) == 0
        out = capsys.readouterr().out
        table = [ln.split()[0] for ln in out.splitlines() if ln.startswith(" ")]
        assert "producer loop" in out
        assert table[:1] == ["fetch"]
        assert {"fetch", "h2d", "enqueue", "unattributed", "h2d_landed"} <= set(
            table
        )
        assert "waits: first " in out and ", steady " in out and ", end " in out
        assert doctor.main([str(prom), "--json"]) == 0
        payload = json.loads(capsys.readouterr().out)
        assert payload["producer_unattributed_frac"] < 0.02
        assert payload["stages"]["h2d_landed"] >= payload["stages"]["h2d"] > 0
        assert set(payload["waits"]) == {"first", "steady", "end"}
        # the same capture as a registry snapshot gives the same table
        snap = PipelineAttribution.from_snapshot(
            get_registry().snapshot()
        ).attribution()
        assert snap["stages"] == payload["stages"]
        assert snap["waits"] == payload["waits"]
