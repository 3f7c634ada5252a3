"""Training flight recorder: pipeline attribution, step anomalies, doctor.

The PR 13 observability layer in one suite: attribution fractions must
partition the step wall (~1.0), bottleneck naming must be deterministic
under the ``loader.fetch``/``loader.h2d`` fault fixtures, an injected
NaN loss must produce a typed ring verdict AND an exit-1 from
``znicz-doctor``, the watch-vector piggyback must compile ZERO new
programs, and the doctor smoke runs against a REAL short training
epoch's ``metrics.prom``.
"""

import json
import math
import time

import numpy as np
import pytest

from znicz_tpu.observability import (
    MetricsRegistry,
    PipelineAttribution,
    StepAnomalyDetector,
    get_registry,
)
from znicz_tpu.observability import anomaly as anomaly_mod
from znicz_tpu.observability import doctor
from znicz_tpu.observability import pipeline
from znicz_tpu.utils import faults
from znicz_tpu.utils.bench_diff import metric_direction
from znicz_tpu.workflow import StandardWorkflow

MLP = [
    {"type": "all2all_tanh", "->": {"output_sample_shape": 16}},
    {"type": "softmax", "->": {"output_sample_shape": 10}},
]


def _stream_workflow(n=512, bs=64, data=None, **kw):
    """Streaming (device_resident=False) stepwise workflow on synthetic
    images — the regime the attribution instrument targets."""
    from znicz_tpu.loader.fullbatch import FullBatchLoader

    gen = np.random.default_rng(0)
    if data is None:
        data = gen.integers(0, 256, (n, 8, 8, 1), dtype=np.uint8)
        norm = {"normalization": "range",
                "normalization_kwargs": {"scale": 255.0, "shift": -0.5}}
    else:
        norm = {}
    labels = gen.integers(0, 10, len(data)).astype(np.int32)
    ld = FullBatchLoader(
        {"train": data}, {"train": labels}, minibatch_size=bs,
        device_resident=False, **norm,
    )
    wf = StandardWorkflow(
        ld, MLP,
        decision_config={"max_epochs": 10000},
        default_hyper={"learning_rate": 0.1, "gradient_moment": 0.9},
        epoch_dispatch="step",
        **kw,
    )
    wf.initialize(seed=7)
    return wf


def _reset_anomaly_gauges():
    """Zero the shared anomaly families so a prior test's detector
    can't leak an active flag into this one's exposition."""
    fams = get_registry().metrics()
    for name in (
        "znicz_train_anomalies_total",
        "znicz_train_anomaly_active",
        "znicz_train_last_loss",
        "znicz_train_last_grad_norm",
    ):
        if name in fams:
            fams[name].reset()


class TestPipelineAttribution:
    def _synthetic_registry(self):
        """30 steps of 0.1 s wall: 2.0 s prefetch-wait (producer busy
        fetching), 0.8 s dispatch, 0.2 s untimed."""
        reg = MetricsRegistry()
        wall = pipeline.step_wall_seconds(reg)
        for _ in range(30):
            wall.observe(0.1)
        wait = reg.histogram(pipeline.WAIT_METRIC)
        for _ in range(30):
            wait.observe(2.0 / 30)
        phase = reg.histogram(pipeline.PHASE_METRIC, labelnames=("phase",))
        phase.labels(phase="dispatch/train").observe(0.8)
        stage = pipeline.stage_seconds(reg)
        stage.labels(stage=pipeline.STAGE_FETCH).observe(1.8)
        stage.labels(stage=pipeline.STAGE_H2D).observe(0.2)
        return reg

    def test_fractions_sum_to_one_on_synthetic_trace(self):
        att = PipelineAttribution.from_registry(
            self._synthetic_registry()
        ).attribution()
        f = att["fractions"]
        assert abs(sum(f.values()) - 1.0) < 0.05
        assert att["type"] == "pipeline"
        assert att["steps"] == 30
        # 2.0 of 3.0 s waiting, producer 90% in fetch -> input-bound
        assert att["verdict"] == "input-bound"
        assert f["prefetch_wait"] == pytest.approx(0.6, abs=0.05)
        assert f["compute"] == pytest.approx(0.8 / 3.0, abs=0.05)
        # h2d carved out of the wait slice by the producer's h2d share
        assert f["h2d"] == pytest.approx(
            (2.0 / 3.0) * (0.2 / 2.0), abs=0.05
        )
        assert att["input_bound_frac"] == pytest.approx(
            f["prefetch_wait"] + f["h2d"]
        )
        assert att["confidence"] in ("low", "medium", "high")

    def test_prometheus_roundtrip_matches_registry(self):
        reg = self._synthetic_registry()
        from_reg = PipelineAttribution.from_registry(reg).attribution()
        from_prom = PipelineAttribution.from_prometheus(
            reg.prometheus_text()
        ).attribution()
        assert from_prom["fractions"] == from_reg["fractions"]
        assert from_prom["verdict"] == from_reg["verdict"]

    def test_snapshot_source_skips_self_describing_riders(self):
        reg = self._synthetic_registry()
        snap = reg.snapshot()
        # the bench attaches {"type": "slo"/"programs"/"pipeline"}
        # records next to the families; the parser must skip them
        snap["slo"] = {"type": "slo", "breached": False}
        snap["pipeline"] = {"type": "pipeline", "verdict": "input-bound"}
        att = PipelineAttribution.from_snapshot(snap).attribution()
        assert att["verdict"] == "input-bound"
        assert att["steps"] == 30

    def test_no_data_verdict(self):
        att = PipelineAttribution.from_registry(
            MetricsRegistry()
        ).attribution()
        assert att["verdict"] == "no-data"
        assert att["input_bound_frac"] == 0.0

    def test_slow_producer_fixture_is_input_bound(self):
        # the CI twin of the acceptance criterion: a deterministically
        # slow producer (loader.fetch delay) must be named input-bound
        wf = _stream_workflow()
        wf.run_epoch()  # compile + warmup
        pipeline.reset_window()
        with faults.injected("loader.fetch", delay=0.02):
            wf.run_epoch()
        att = PipelineAttribution.from_registry().attribution()
        assert att["verdict"] == "input-bound"
        assert abs(sum(att["fractions"].values()) - 1.0) < 0.05
        assert att["input_bound_frac"] > 0.5
        assert att["fractions"]["prefetch_wait"] > att["fractions"]["h2d"]

    def test_slow_h2d_fixture_is_h2d_bound(self):
        wf = _stream_workflow()
        wf.run_epoch()
        pipeline.reset_window()
        with faults.injected("loader.h2d", delay=0.02):
            wf.run_epoch()
        att = PipelineAttribution.from_registry().attribution()
        assert att["verdict"] == "h2d-bound"
        assert abs(sum(att["fractions"].values()) - 1.0) < 0.05
        assert att["fractions"]["h2d"] > att["fractions"]["prefetch_wait"]
        # the probe's bandwidth gauge reflects the injected slowness
        assert att["h2d_bytes_per_second"] is not None

    def test_prefetch_stage_split_and_queue_full_counter(self):
        from znicz_tpu.loader.prefetch import prefetch

        pipeline.reset_window()
        # depth 1 + slow consumer: the producer finds the queue full
        out = []
        for item in prefetch(iter(range(8)), depth=1):
            time.sleep(0.01)
            out.append(item)
        assert out == list(range(8))
        reg = get_registry()
        stage = reg.metrics()[pipeline.STAGE_METRIC]
        by = {
            k[0]: child for k, child in stage.children().items()
        }
        assert by[pipeline.STAGE_FETCH].count >= 8
        assert by[pipeline.STAGE_ENQUEUE].count >= 8
        # the producer stalled on a full queue, and that is DISTINCT
        # from a slow producer: enqueue carries the stall time
        assert reg.metrics()[pipeline.QUEUE_FULL_METRIC].value > 0
        assert by[pipeline.STAGE_ENQUEUE].sum > by[pipeline.STAGE_FETCH].sum

    def test_prefetch_transform_stage_and_results(self):
        from znicz_tpu.loader.prefetch import prefetch

        pipeline.reset_window()
        out = list(
            prefetch(iter(range(6)), depth=2, transform=lambda x: x * 2)
        )
        assert out == [0, 2, 4, 6, 8, 10]
        stage = get_registry().metrics()[pipeline.STAGE_METRIC]
        by = {k[0]: c for k, c in stage.children().items()}
        assert by[pipeline.STAGE_TRANSFORM].count == 6

    def test_h2d_probe_bandwidth_gauge(self):
        reg = MetricsRegistry()
        probe = pipeline.H2DProbe(reg)
        probe.observe(1_000_000, 0.1)  # 10 MB/s
        assert reg.metrics()[
            pipeline.H2D_BPS_METRIC
        ].value == pytest.approx(1e7, rel=0.01)
        assert reg.metrics()[pipeline.H2D_BYTES_METRIC].value == 1e6


class TestAnomalyDetector:
    def test_loss_spike_robust_z(self):
        reg = MetricsRegistry()
        det = StepAnomalyDetector(registry=reg, min_history=8)
        for i in range(20):
            out = det.observe_step(i, loss=1.0 + 0.01 * (i % 3))
            assert out == []
        raised = det.observe_step(20, loss=50.0)
        assert [a["type"] for a in raised] == [anomaly_mod.LOSS_SPIKE]
        assert raised[0]["zscore"] > det.z_threshold
        assert det.active
        rep = det.report()
        assert rep["counts"] == {anomaly_mod.LOSS_SPIKE: 1}
        # the flight-recorder snapshot carries the lead-in steps
        assert len(rep["ring"]) == 1
        assert rep["ring"][0]["snapshot"][-1]["step"] == 19

    def test_step_time_regression_and_active_decay(self):
        reg = MetricsRegistry()
        det = StepAnomalyDetector(
            registry=reg, min_history=8, active_window=5
        )
        for i in range(15):
            det.observe_step(i, loss=1.0, step_seconds=0.01)
        # one slow step is a blip, not a regression: no verdict yet
        assert det.observe_step(15, loss=1.0, step_seconds=0.5) == []
        assert det.observe_step(16, loss=1.0, step_seconds=0.5) == []
        raised = det.observe_step(17, loss=1.0, step_seconds=0.5)
        assert [a["type"] for a in raised] == [
            anomaly_mod.STEP_TIME_REGRESSION
        ]
        assert det.active
        for i in range(18, 24):  # active_window steps later: cleared
            det.observe_step(i, loss=1.0, step_seconds=0.01)
        assert not det.active
        assert reg.metrics()["znicz_train_anomaly_active"].value == 0.0

    def test_non_finite_grad_norm_typed(self):
        det = StepAnomalyDetector(registry=MetricsRegistry())
        raised = det.observe_step(
            0, loss=1.0, grad_norm=float("inf")
        )
        assert [a["type"] for a in raised] == [anomaly_mod.NON_FINITE_GRAD]

    def test_ring_is_bounded(self):
        det = StepAnomalyDetector(
            registry=MetricsRegistry(), ring_size=4
        )
        for i in range(9):
            det.observe_step(i, loss=float("nan"))
        rep = det.report()
        assert len(rep["ring"]) == 4
        assert rep["counts"][anomaly_mod.NON_FINITE_LOSS] == 9
        assert rep["ring"][-1]["step"] == 8
        json.dumps(rep)  # JSON-able end to end

    def test_nan_baseline_does_not_mute_detection(self):
        # a NaN loss must not poison the rolling median: later finite
        # spikes still detect
        det = StepAnomalyDetector(
            registry=MetricsRegistry(), min_history=8
        )
        det.observe_step(0, loss=float("nan"))
        for i in range(1, 15):
            det.observe_step(i, loss=1.0)
        raised = det.observe_step(15, loss=100.0)
        assert anomaly_mod.LOSS_SPIKE in [a["type"] for a in raised]


class TestNanFlightRecorder:
    def test_injected_nan_loss_rings_and_doctor_exits_1(
        self, tmp_path, capsys
    ):
        from znicz_tpu.services.web_status import StatusWriter

        _reset_anomaly_gauges()
        # poison a late batch so the detector has a healthy lead-in
        data = np.random.default_rng(3).normal(
            size=(256, 8, 8, 1)
        ).astype(np.float32)
        data[200:] = np.nan
        wf = _stream_workflow(data=data, bs=32)
        sw = StatusWriter(str(tmp_path))
        wf.services.append(sw)
        verdict = wf.run_epoch()
        assert verdict is not None
        rep = wf.anomaly.report()
        assert rep["active"]
        assert rep["counts"].get(anomaly_mod.NON_FINITE_LOSS, 0) >= 1
        # the loader shuffles, so the FIRST poisoned batch may land at
        # step 0 (empty lead-in) — the latest entry always has one
        entry = [
            e for e in rep["ring"]
            if e["type"] == anomaly_mod.NON_FINITE_LOSS
        ][-1]
        assert entry["snapshot"], "ring entry must carry the lead-in"
        # the flight recorder surfaced through status.json ...
        status = json.loads((tmp_path / "status.json").read_text())
        assert status["anomalies"]["active"]
        assert status["anomalies"]["counts"]
        assert status["pipeline"]["type"] == "pipeline"
        # ... and through /metrics -> znicz-doctor gates exit 1
        prom = tmp_path / "metrics.prom"
        assert prom.exists()
        assert doctor.main([str(prom)]) == 1
        out = capsys.readouterr().out
        assert "ACTIVE" in out
        assert doctor.main([str(prom), "--json"]) == 1
        payload = json.loads(capsys.readouterr().out)
        assert payload["anomalies"]["active"] is True
        assert payload["anomalies"]["counts"].get(
            anomaly_mod.NON_FINITE_LOSS, 0
        ) >= 1
        _reset_anomaly_gauges()


class TestZeroNewPrograms:
    def test_watch_piggyback_compiles_nothing_new(self):
        # the acceptance pin: the grad-norm/attribution instrumentation
        # adds ZERO compiled programs — nothing lands in the PR 11
        # device ledger / znicz_serve_compiles_total, and the train
        # step stays ONE jit cache entry with the watch output riding
        # the existing program
        from znicz_tpu.observability import device

        ledger_before = device.program_count()
        compiles = get_registry().counter(
            "znicz_serve_compiles_total",
            "distinct compiled engine programs by kind and bucket",
            ("kind", "bucket"),
        )
        compiles_before = sum(
            c.value for c in compiles.children().values()
        )
        compile_hist = get_registry().metrics().get(
            "znicz_compile_seconds"
        )
        compile_obs_before = (
            sum(c.count for c in compile_hist.children().values())
            if compile_hist is not None
            else 0
        )
        wf = _stream_workflow(n=128, bs=64)  # detector ON by default
        assert wf.anomaly is not None
        wf.run_epoch()
        wf.run_epoch()
        assert wf._train_step._cache_size() == 1
        off = _stream_workflow(n=128, bs=64, anomaly=False)
        assert off.anomaly is None
        off.run_epoch()
        assert off._train_step._cache_size() == 1
        assert device.program_count() == ledger_before
        assert (
            sum(c.value for c in compiles.children().values())
            == compiles_before
        )
        compile_hist = get_registry().metrics().get(
            "znicz_compile_seconds"
        )
        compile_obs_after = (
            sum(c.count for c in compile_hist.children().values())
            if compile_hist is not None
            else 0
        )
        assert compile_obs_after == compile_obs_before

    def test_scan_path_feeds_detector_without_extra_programs(self):
        # scanned dispatch: watches stack inside the ONE scan program
        # and drain at the epoch sync
        from znicz_tpu.loader.fullbatch import FullBatchLoader

        gen = np.random.default_rng(1)
        imgs = gen.integers(0, 256, (256, 8, 8, 1), dtype=np.uint8)
        labels = gen.integers(0, 10, 256).astype(np.int32)
        ld = FullBatchLoader(
            {"train": imgs}, {"train": labels}, minibatch_size=64,
            normalization="range",
            normalization_kwargs={"scale": 255.0, "shift": -0.5},
            device_resident=True,
        )
        wf = StandardWorkflow(
            ld, MLP,
            decision_config={"max_epochs": 10000},
            default_hyper={"learning_rate": 0.1},
            epoch_dispatch="scan",
        )
        wf.initialize(seed=5)
        wf.run_epoch()
        assert wf._train_epoch_scan._cache_size() == 1
        rep = wf.anomaly.report()
        assert rep["last_step"] == 3  # 4 scan steps fed, 0-indexed
        assert rep["total"] == 0  # healthy run


class TestDoctorCLI:
    def test_smoke_on_real_epoch_metrics_prom(self, tmp_path, capsys):
        # the tier-1 CI smoke: a real short training epoch writes
        # metrics.prom; the doctor parses it, prints a verdict, exit 0
        from znicz_tpu.services.web_status import StatusWriter

        _reset_anomaly_gauges()
        pipeline.reset_window()
        wf = _stream_workflow(n=256, bs=32)
        sw = StatusWriter(str(tmp_path))
        wf.services.append(sw)
        wf.run_epoch()
        rc = doctor.main([str(tmp_path / "metrics.prom")])
        out = capsys.readouterr().out
        assert rc == 0
        assert "anomalies:" in out
        assert "-bound" in out or "unattributed" in out
        rc = doctor.main([str(tmp_path / "metrics.prom"), "--json"])
        payload = json.loads(capsys.readouterr().out)
        assert rc == 0
        assert payload["type"] == "pipeline"
        assert payload["verdict"] != "no-data"
        assert abs(sum(payload["fractions"].values()) - 1.0) < 0.05
        assert payload["steps"] >= 8

    def test_usage_errors_exit_2(self, tmp_path, capsys):
        assert doctor.main([]) == 2
        assert doctor.main(["a", "b"]) == 2
        assert doctor.main(["--instance"]) == 2
        assert doctor.main([str(tmp_path / "missing.prom")]) == 2
        bad = tmp_path / "bad.prom"
        bad.write_text("this is { not an exposition !!!\n")
        assert doctor.main([str(bad)]) == 2
        capsys.readouterr()

    def test_no_data_source_is_healthy(self, tmp_path, capsys):
        reg = MetricsRegistry()
        reg.counter("some_counter_total", "x").inc()
        p = tmp_path / "m.prom"
        p.write_text(reg.prometheus_text())
        assert doctor.main([str(p)]) == 0
        assert "no-data" in capsys.readouterr().out

    def test_instance_filter_scopes_fleet_exposition(self, tmp_path):
        # two instances in one exposition (the aggregator's merged
        # /metrics): --instance must attribute only the wanted one
        lines = []
        for inst, wall in (("a", 1.0), ("b", 9.0)):
            lines += [
                "znicz_train_step_wall_seconds_bucket"
                f'{{instance="{inst}",le="+Inf"}} 10',
                f'znicz_train_step_wall_seconds_sum{{instance="{inst}"}}'
                f" {wall}",
                "znicz_train_step_wall_seconds_count"
                f'{{instance="{inst}"}} 10',
            ]
        text = (
            "# TYPE znicz_train_step_wall_seconds histogram\n"
            + "\n".join(lines) + "\n"
        )
        att = PipelineAttribution.from_prometheus(
            text, instance="a"
        ).attribution()
        assert att["wall_seconds"] == pytest.approx(1.0)
        both = PipelineAttribution.from_prometheus(text).attribution()
        assert both["wall_seconds"] == pytest.approx(10.0)


def _loop_ledger():
    """{stage: (count, sum)} of the serving thread's stage clock, and the
    (count, sum) of its iterations."""
    fams = get_registry().metrics()
    stages = {
        k[0]: (c.count, c.sum)
        for k, c in fams[pipeline.SERVE_LOOP_STAGE_METRIC].children().items()
    }
    turns = fams[pipeline.SERVE_LOOP_ITERATION_METRIC].children()[()]
    return stages, (turns.count, turns.sum)


class TestTickStages:
    def test_stage_shares_sum_to_at_most_one_and_decode_is_present(self):
        from znicz_tpu.core import prng
        from znicz_tpu.services.engine import PagedDecodeEngine
        from znicz_tpu.workflow.transformer import init_lm_params

        prng.seed_all(27)
        params = init_lm_params(17, 32, 2, 4, max_seq=64)
        eng = PagedDecodeEngine(
            params, n_heads=4, eos_id=14, batch_size=2, admit_every=4
        )
        # the registry families are process-wide — zero them so earlier
        # engine tests' ticks don't skew the comparison below
        for name in (
            pipeline.SERVE_LOOP_STAGE_METRIC,
            pipeline.SERVE_LOOP_ITERATION_METRIC,
        ):
            get_registry().metrics()[name].reset()
        gen = np.random.default_rng(3)
        for _ in range(3):
            eng.submit(gen.integers(0, 17, (6,)).astype(np.int32), 8)
        eng.run()
        stages, (ticks, wall) = _loop_ledger()
        assert ticks > 0 and wall > 0
        share = {k: s / wall for k, (_, s) in stages.items()}
        assert sum(share.values()) <= 1.0 + 1e-6
        decode = [k for k in share if k.startswith("serve/decode/")]
        assert sum(share[k] for k in decode) > 0
        # one decode chunk a tick here: each of its parts lapped once
        assert {stages[k][0] for k in decode} == {ticks}
        assert not [k for k in share if k.startswith("serve/verify/")]

    def test_a_verify_chunk_books_under_serve_verify(self):
        from znicz_tpu.core import prng
        from znicz_tpu.services.engine import PagedDecodeEngine
        from znicz_tpu.workflow.transformer import init_lm_params

        prng.seed_all(27)
        params = init_lm_params(17, 32, 2, 4, max_seq=128)
        eng = PagedDecodeEngine(
            params, n_heads=4, eos_id=16, batch_size=2,
            block_size=8, n_blocks=64, spec_k=4,
        )
        get_registry().metrics()[pipeline.SERVE_LOOP_STAGE_METRIC].reset()
        # repeat-heavy prompt: prompt-lookup drafts, verify ticks run
        prompt = np.tile(
            np.array([1, 2, 3, 4], np.int32), 6
        )
        eng.submit(prompt, 16)
        eng.run()
        stages, _ = _loop_ledger()
        verify_steps = eng.stats()["spec"]["verify_steps"]
        assert stages["serve/verify/draft"][0] > 0
        for part in ("grow", "dispatch", "wait", "fetch", "emit"):
            got = stages.get(f"serve/verify/{part}", (0, 0.0))[0]
            assert got == verify_steps


class TestBenchDiffMarkers:
    def test_bound_frac_is_lower_better(self):
        assert metric_direction(
            "train_input_bound_frac", set(), set()
        ) == "lower"

    def test_bytes_per_second_is_higher_better(self):
        assert metric_direction(
            "train_h2d_bytes_per_second", set(), set()
        ) == "higher"


class TestResetWindowInteraction:
    def test_phase_timer_survives_warmup_reset(self):
        # reset_window() clears znicz_train_phase_seconds; a PhaseTimer
        # holding a pre-reset baseline must fall back to the fresh
        # series instead of reporting empty/negative windows
        # (status.json["timing"] reads summary())
        from znicz_tpu.observability import PhaseTimer

        timer = PhaseTimer(pipeline.PHASE_METRIC)
        with timer.phase("dispatch/train"):
            time.sleep(0.002)
        assert "dispatch/train" in timer.summary()
        pipeline.reset_window()
        with timer.phase("dispatch/train"):
            time.sleep(0.002)
        s = timer.summary()["dispatch/train"]
        assert s["count"] == 1
        assert s["total_s"] > 0

    def test_anomaly_off_watch_is_none_on_device(self):
        # anomaly=False must remove the watch output entirely (XLA can
        # then DCE the norm), not just skip the host read
        wf = _stream_workflow(n=128, bs=64, anomaly=False)
        mb = next(iter(wf.loader.batches("train")))
        import jax.numpy as jnp

        _, _, watch = wf._train_step(
            wf.state, jnp.asarray(mb.data), jnp.asarray(mb.labels),
            jnp.asarray(mb.mask), 1.0, wf._acc_init(), wf._ctx,
        )
        assert watch is None


class TestWatchVector:
    def test_stepwise_detector_sees_losses_and_grad_norms(self):
        wf = _stream_workflow(n=256, bs=32)
        wf.run_epoch()
        rep = wf.anomaly.report()
        assert rep["last_step"] == 7  # 8 train steps, 0-indexed
        # gauges carry finite last-step values
        assert math.isfinite(
            get_registry().metrics()["znicz_train_last_loss"].value
        )
        assert (
            get_registry().metrics()["znicz_train_last_grad_norm"].value
            > 0
        )
