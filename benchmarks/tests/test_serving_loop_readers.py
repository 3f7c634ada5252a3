"""The seven readers of the serving thread's ledger (PR 38): each on a
hand-made window delta (a number where its series are there, None where
they are not, which is what a parent commit gives), and all of them on
what the program itself observes in a toy serving run, so that a renamed
stage or family is caught here and not on the chip."""

import pytest

import toy
from drivers_access import serve
from harness import loading, registry, serving_loop

STAGES = serving_loop.STAGES


def _hist(name, total, count=1, **labels):
    key = (name, tuple(sorted(labels.items())))
    return key, {"sum": total, "count": count}


def _read(metric, delta):
    reader = loading.load_module("layer_metrics", metric)
    return reader.read({"registry": delta})


# 10 turns of the thread, 8 of them with a decode chunk, 2 with a verify
# chunk; 30 prefill chunks of which 4 were a prompt's last
HAND_MADE = registry.Delta({}, dict([
    _hist(STAGES, 0.004, 10, stage="frontdoor/control"),
    _hist(STAGES, 0.002, 10, stage="frontdoor/pump"),
    _hist(STAGES, 0.010, 10, stage="frontdoor/stream"),
    _hist(STAGES, 0.004, 20, stage="frontdoor/housekeeping"),
    _hist(STAGES, 0.005, 10, stage="serve/schedule"),
    _hist(STAGES, 0.060, 30, stage="serve/prefill/host"),
    _hist(STAGES, 0.040, 4, stage="serve/prefill/wait"),
    _hist(STAGES, 0.003, 8, stage="serve/decode/grow"),
    _hist(STAGES, 0.012, 8, stage="serve/decode/prepare"),
    _hist(STAGES, 0.008, 8, stage="serve/decode/dispatch"),
    _hist(STAGES, 0.240, 8, stage="serve/decode/wait"),
    _hist(STAGES, 0.016, 8, stage="serve/decode/fetch"),
    _hist(STAGES, 0.020, 8, stage="serve/decode/emit"),
    _hist(STAGES, 0.002, 10, stage="serve/verify/draft"),
    _hist(STAGES, 0.001, 2, stage="serve/verify/grow"),
    _hist(STAGES, 0.002, 4, stage="serve/verify/prepare"),
    _hist(STAGES, 0.002, 2, stage="serve/verify/dispatch"),
    _hist(STAGES, 0.020, 2, stage="serve/verify/wait"),
    _hist(STAGES, 0.001, 2, stage="serve/verify/fetch"),
    _hist(STAGES, 0.008, 2, stage="serve/verify/emit"),
    _hist(serving_loop.TURNS, 0.500, 10),
    _hist("znicz_serve_decode_period_seconds", 0.450, 9),
    _hist("znicz_serve_prefill_chunks_between_decodes", 27.0, 9),
    _hist("znicz_serve_engine_queue_wait_seconds", 0.6, 4),
]))


@pytest.mark.parametrize(
    "metric, expected",
    [
        ("engine.decode_period_ms", 50.0),
        ("engine.prefill_chunks_per_decode_chunk", 3.0),
        # (0.040 + 0.240 + 0.020) s over 8 + 2 chunks
        ("engine.wait_ms_per_decode_chunk", 30.0),
        # every serve/* stage that is no wait: 0.140 s over 10 chunks
        ("engine.host_ms_per_decode_chunk", 14.0),
        ("frontdoor.host_ms_per_turn", 2.0),
        # 0.460 s of stages in 0.500 s of turns
        ("engine.loop_unattributed_pct", 8.0),
        ("engine.queue_wait_mean_ms", 150.0),
    ],
)
def test_reader_on_a_hand_made_window(metric, expected):
    assert _read(metric, HAND_MADE) == pytest.approx(expected)
    assert _read(metric, registry.Delta({}, {})) is None


def test_the_readers_name_every_stage_the_program_has_and_no_other():
    from znicz_tpu.observability import pipeline

    named = serving_loop.WAITS + serving_loop.ENGINE_HOST + serving_loop.FRONTDOOR
    assert sorted(named) == sorted(pipeline.SERVE_LOOP_STAGES)


def test_readers_on_what_the_program_observes(tmp_path):
    run = toy.make_run(
        "toy-serve", toy.serve_workload(), toy.lm_config(),
        cache_dir=tmp_path, seconds=2.0,
    )
    obs = serve.run(run)["observations"]
    read = {
        m: _read(m, obs["registry"])
        for m in (
            "engine.decode_period_ms", "engine.prefill_chunks_per_decode_chunk",
            "engine.wait_ms_per_decode_chunk", "engine.host_ms_per_decode_chunk",
            "frontdoor.host_ms_per_turn", "engine.loop_unattributed_pct",
            "engine.queue_wait_mean_ms",
        )
    }
    assert None not in read.values(), read
    # the stages tile a turn exactly; the window's two readings each cut
    # one turn in two
    assert read["engine.loop_unattributed_pct"] == pytest.approx(0.0, abs=1.0)
    for name in (
        "engine.decode_period_ms", "engine.wait_ms_per_decode_chunk",
        "engine.host_ms_per_decode_chunk", "frontdoor.host_ms_per_turn",
    ):
        assert read[name] > 0, read
    assert read["engine.prefill_chunks_per_decode_chunk"] >= 0
    # a period is a turn of the thread: its parts are the turn's, less
    # the turns in which nothing decoded (their prefill has no period)
    parts = (
        read["engine.wait_ms_per_decode_chunk"]
        + read["engine.host_ms_per_decode_chunk"]
        + read["frontdoor.host_ms_per_turn"]
    )
    assert read["engine.decode_period_ms"] <= 1.5 * parts, read


def test_the_ledger_tool_prints_the_window_stage_by_stage():
    tool = loading.load_module("tools", "serving_loop_ledger")
    text = tool.table(HAND_MADE)
    rows = {line.split()[0]: line.split()[1:] for line in text.splitlines()[1:21]}
    assert len(rows) == 20
    # laps, seconds, ms a chunk (10 of them), ms a turn (10)
    assert rows["serve/decode/wait"] == ["8", "0.2400", "24.000", "24.000"]
    assert rows["frontdoor/housekeeping"] == ["20", "0.0040", "0.400", "0.400"]
    assert "decode and verify chunks: 10; turns with work: 10" in text
