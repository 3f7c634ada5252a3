"""The per-layer readers of PR 24's program counters: each on a hand-made
window delta (a number where its series is there, None where it is not),
and all of the ``input.*`` ones on what the program itself observes in a
toy run, streamed and resident, so that a renamed series or label is
caught here and not on the chip."""

import pytest

import toy
from drivers_access import train
from harness import loading, registry

STAGES = "znicz_pipeline_stage_seconds"
WAITS = "znicz_prefetch_wait_seconds"


def _hist(name, total, count=1, **labels):
    key = (name, tuple(sorted(labels.items())))
    return key, {"sum": total, "count": count}


def _value(name, value, **labels):
    return (name, tuple(sorted(labels.items()))), {"value": value}


def _delta(*series):
    return registry.Delta({}, dict(series))


def _read(metric, delta, steps=10):
    reader = loading.load_module("layer_metrics", metric)
    return reader.read({"registry": delta, "steps": steps})


HAND_MADE = _delta(
    _hist(STAGES, 0.80, 11, stage="fetch"),
    _hist(STAGES, 0.70, 10, stage="crop"),
    _hist(STAGES, 0.02, 10, stage="crop_params"),
    _hist(STAGES, 0.05, 10, stage="h2d"),
    _hist(STAGES, 0.10, 10, stage="enqueue"),
    _hist(STAGES, 0.30, 10, stage="h2d_landed"),
    _hist("znicz_pipeline_producer_seconds", 1.00, 11),
    _hist(WAITS, 0.06, 1, at="first"),
    _hist(WAITS, 0.20, 9, at="steady"),
    _hist(WAITS, 0.01, 1, at="end"),
    _hist("znicz_serve_frontdoor_queue_wait_seconds", 1.2, 4),
    _value("znicz_serve_decode_steps_total", 16.0),
    _value("znicz_serve_decode_gathered_tokens_total", 16.0 * 32 * 8 * 32),
    _value("znicz_serve_decode_chunks_total", 2.0, window="8"),
)


@pytest.mark.parametrize(
    "metric, expected",
    [
        ("input.fetch_ms_per_step", 80.0),
        ("input.crop_ms_per_step", 70.0),
        ("input.h2d_landed_ms_per_step", 30.0),
        ("input.wait_steady_ms_per_step", 20.0),
        ("input.wait_epoch_edge_ms_per_step", 7.0),
        # 1 - (0.80 + 0.05 + 0.10) / 1.00: crop, crop_params and
        # h2d_landed are not parts of the sum
        ("input.producer_unattributed_pct", 5.0),
        ("frontdoor.queue_wait_mean_ms", 300.0),
        ("decode.gathered_tokens_per_step", 32 * 8 * 32),
    ],
)
def test_reader_on_a_hand_made_window(metric, expected):
    assert _read(metric, HAND_MADE) == pytest.approx(expected)
    assert _read(metric, _delta()) is None


def test_the_waits_by_position_sum_to_the_wait_the_old_reader_reads():
    whole = _read("input.wait_ms_per_step", HAND_MADE)
    parts = _read("input.wait_steady_ms_per_step", HAND_MADE) + _read(
        "input.wait_epoch_edge_ms_per_step", HAND_MADE
    )
    assert parts == pytest.approx(whole)


def test_no_steps_no_number():
    for metric in (
        "input.fetch_ms_per_step", "input.crop_ms_per_step",
        "input.h2d_landed_ms_per_step", "input.wait_steady_ms_per_step",
        "input.wait_epoch_edge_ms_per_step",
    ):
        assert _read(metric, HAND_MADE, steps=0) is None


@pytest.mark.parametrize("loader_mode", ["streamed", "resident"])
def test_input_readers_on_what_the_program_observes(tmp_path, loader_mode):
    cfg = toy.cnn_config()
    toy.point_model_file_at(cfg)
    # stepwise, as alexnet-resident runs it: a scanned epoch has no
    # producer loop to observe
    workload = toy.train_workload(
        loader_mode=loader_mode, epoch_dispatch="step"
    )
    run = toy.make_run(
        "toy-train", workload, cfg, cache_dir=tmp_path, seconds=0.2
    )
    live = train.setup(run)
    w = train.window(run, live)
    delta, steps = w["delta"], w["steps"]
    assert steps >= 8

    fetch = _read("input.fetch_ms_per_step", delta, steps)
    landed = _read("input.h2d_landed_ms_per_step", delta, steps)
    steady = _read("input.wait_steady_ms_per_step", delta, steps)
    edges = _read("input.wait_epoch_edge_ms_per_step", delta, steps)
    hole = _read("input.producer_unattributed_pct", delta, steps)
    crop = _read("input.crop_ms_per_step", delta, steps)
    assert fetch > 0 and landed > 0 and steady >= 0 and edges > 0
    assert hole == pytest.approx(0.0, abs=0.5)
    if loader_mode == "streamed":
        assert 0 < crop <= fetch
    else:
        assert crop is None  # a resident pool cuts no crop on the host
    whole = _read("input.wait_ms_per_step", delta, steps)
    assert steady + edges == pytest.approx(whole, rel=1e-6)
