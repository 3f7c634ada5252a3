"""The input path accounts for its own time (PR 24).

The producer's stages tile its loop (their sum is the producer's own
total, with or without a fault armed inside a stage; time outside every
stage shows as ``producer_unattributed_frac`` and lowers the verdict's
confidence), waits are labelled by where in the epoch they fall, a
copy's landing time is taken beside the loop without delaying the
hand-off, and spans reach the profiler's annotations with the tracer
idle.
"""

import sys
import threading
import time

import pytest

from test_flight_recorder import _stream_workflow
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
        pipeline.reset_window()
        if fault is None:
            wf.run_epoch()
        else:
            with faults.injected(fault, delay=0.01):
                wf.run_epoch()
        sums = _stage_sums()
        count, total = _producer()
        steps = 512 // 64
        assert count == steps + 1  # the sentinel's fetch is an iteration
        tiled = sum(sums.get(s, (0, 0.0))[1] for s in pipeline.TILING_STAGES)
        assert tiled == pytest.approx(total, rel=0.02)
        assert sums[pipeline.STAGE_FETCH][0] == steps + 1
        assert sums[pipeline.STAGE_H2D][0] == steps
        assert sums[pipeline.STAGE_ENQUEUE][0] == steps
        if fault is not None:
            # the armed delay is inside the stage it is named after
            stage = fault.split(".")[1]
            assert sums[stage][1] >= steps * 0.01
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
        assert wf._h2d_probe.drain()
        pipeline.reset_window()
        wf.run_epoch()
        assert wf._h2d_probe.drain()
        sums = _stage_sums()
        assert sums["h2d_landed"][0] == sums["h2d"][0] == 512 // 64
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
        import json

        from znicz_tpu.observability import doctor

        wf = _stream_workflow(n=256, bs=32)
        wf.run_epoch()
        assert wf._h2d_probe.drain()
        pipeline.reset_window()
        wf.run_epoch()
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
