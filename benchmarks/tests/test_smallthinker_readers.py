"""The readers ``smallthinker-serve-mixed-lengths`` adds, on recorded
registry deltas and a recorded reduction: a number where their series are
there, None where they are not (a parent commit without the counters, a
run without a trace), and the operation counts behind them against the
configuration's own arithmetic."""

import pytest

import toy  # noqa: F401  (puts benchmarks/ on the path)
from harness import loading, registry, smallthinker_work as work
from harness.peaks import PEAKS

CFG = loading.load_json("configs", "smallthinker-21b-l8.json")
PEAK = PEAKS["TPU v5 lite"]


def _value(name, value, **labels):
    return (name, tuple(sorted(labels.items()))), {"value": value}


# a window of 100 decode chunks of 8 steps, 16 live rows of 5,120 tokens:
# a global layer reads 81,920 rows a step in place, a window layer gathers
# its ring for all 64 slots; 96 pairs a layer, 50 experts hit
STEPS, LAYER_STEPS = 800.0, 6400.0
GLOBAL_ROWS, WINDOW_ROWS = 16 * 5120.0, 64 * 34 * 128.0
RECORDED = dict([
    _value("znicz_serve_decode_steps_total", STEPS),
    _value("znicz_serve_decode_cached_rows_total", STEPS * GLOBAL_ROWS, kind="global"),
    _value("znicz_serve_decode_cached_rows_total", STEPS * WINDOW_ROWS, kind="window"),
    _value("znicz_serve_decode_gathered_tokens_total",
           STEPS * (2 * GLOBAL_ROWS + 6 * WINDOW_ROWS) / 8),
    _value("znicz_serve_moe_layer_steps_total", LAYER_STEPS, phase="decode"),
    _value("znicz_serve_moe_layer_steps_total", 900.0, phase="prefill"),
    _value("znicz_serve_moe_idle_experts_total", LAYER_STEPS * 14.0, phase="decode"),
    _value("znicz_serve_preemptions_total", 3.0),
    _value("znicz_serve_requests_admitted_total", 24.0),
    (("znicz_serve_cache_bytes_per_resident_token", ()),
     {"sum": 4000 * 9000.0, "count": 4000}),
] + [
    _value("znicz_serve_moe_pairs_total", LAYER_STEPS * 96.0 / 64, phase="decode",
           expert=str(e))
    for e in range(64)
])
COUNTERS = (
    "moe.experts_hit_per_layer", "cache.bytes_per_resident_token",
    "engine.preemptions_per_request",
)
ROOFLINES = (
    "attn.window_decode_roofline_pct", "attn.global_decode_roofline_pct",
    "moe.reglu_experts_roofline_pct",
)


def _obs(series=RECORDED, scoped="whole", trace=True, cfg=CFG):
    """What the driver hands the readers.  ``scoped``: the decode program
    with one whole execution of 8 steps in the trace, or a table of its
    own."""
    if scoped == "whole":
        scoped = {
            "jit__paged_decode_chunk": {
                "whole_executions": 1, "steps": 8, "device_s": 0.2,
                "scopes": {"moe_experts": 0.06, "attn_window": 0.09,
                           "attn_global": 0.004},
            },
            "jit__paged_prefill_prog": {
                "whole_executions": 9, "steps": 9, "device_s": 0.3,
                "scopes": {"moe_experts": 9.9, "attn_window": 9.9},
            },
        }
    delta = registry.Delta({}, series)
    return {
        "registry": delta, "traced_registry": delta if trace else None,
        "config": cfg, "peaks": PEAK,
        "decode_program": "jit__paged_decode_chunk",
        "trace": {"programs": {}} if trace else None,
        "scoped": scoped if trace else None,
    }


def _read(metric, obs):
    return loading.load_module("layer_metrics", metric).read(obs)


def test_counter_readers_on_a_recorded_delta():
    assert _read("moe.experts_hit_per_layer", _obs()) == pytest.approx(50.0)
    assert _read("cache.bytes_per_resident_token", _obs()) == pytest.approx(9000.0)
    assert _read("engine.preemptions_per_request", _obs()) == pytest.approx(0.125)


def test_scope_rooflines_read_the_marked_operations_of_the_decode_program():
    experts = work.experts_product(CFG, 50.0, 96.0)
    want = 100.0 * work.least_seconds(experts, PEAK) * 8 * 8 / 0.06
    assert _read("moe.reglu_experts_roofline_pct", _obs()) == pytest.approx(want)
    assert 0 < want < 100
    for kind, rows, layers, seconds in (
        ("window", WINDOW_ROWS, 6, 0.09), ("global", GLOBAL_ROWS, 2, 0.004),
    ):
        attn = work.gqa_attention(CFG, rows)
        want = 100.0 * work.least_seconds(attn, PEAK) * layers * 8 / seconds
        got = _read(f"attn.{kind}_decode_roofline_pct", _obs())
        assert got == pytest.approx(want) and 0 < got < 100


def test_a_trace_that_holds_only_stubs_of_the_decode_program_reads_nothing():
    stubs = {"jit__paged_decode_chunk": {
        "whole_executions": 0, "steps": 0, "device_s": 0.0, "scopes": {}}}
    for metric in ROOFLINES:
        assert _read(metric, _obs(scoped=stubs)) is None


@pytest.mark.parametrize("metric", COUNTERS[:2] + ROOFLINES)
def test_a_program_without_the_counters_reads_nothing(metric):
    """What the parent commit gives: the engine's older series, none of
    the new ones, and a trace whose operations carry no marked scope."""
    older = {
        k: v for k, v in RECORDED.items()
        if not any(new in k[0] for new in ("moe", "cached_rows", "resident_token"))
    }
    assert _read(metric, _obs(series=older, scoped={})) is None


@pytest.mark.parametrize("metric", ROOFLINES + COUNTERS[:1])
def test_another_towers_configuration_reads_nothing(metric):
    """The readers' keys are this configuration's: handed ``axk1-ep16``
    they give nothing rather than a number from the wrong arithmetic."""
    other = loading.load_json("configs", "axk1-ep16.json")
    assert _read(metric, _obs(cfg=other)) is None


@pytest.mark.parametrize("metric", ROOFLINES)
def test_a_run_without_a_trace_reads_no_device_metric(metric):
    assert _read(metric, _obs(trace=False)) is None


def test_operation_counts_follow_the_configuration():
    held = CFG["parameters_held"]
    assert work.layers_of(CFG) == {"window": 6, "global": 2}
    assert work.expert_params(CFG) == held["an_expert"] == 3 * 2560 * 768
    assert work.attention_params(CFG) == held["attention_a_layer"]
    # every weight but the embedding and the experts, read once a step
    assert work.always_read_params(CFG) == (
        held["total"] - CFG["vocab_size"] * CFG["hidden_size"]
        - 8 * held["experts_a_layer"]
    )
    assert work.cache_row_bytes(CFG) == 2048
    # the issue's count: 16 rows at a mean context of 5k read ~4.7 GB of
    # expert weights (50 of 64 a layer), 0.7 GB of attention weights and
    # head, ~1.0 GB of cached K/V
    step = work.decode_step_bytes(
        CFG, 50.0, {"global": 16 * 5120.0, "window": 16 * 4224.0}
    )
    assert step == pytest.approx(4.72e9 + 1.12e9 + 1.17e9, rel=0.02)
    # one table a row would keep 16,384 B a token; the kinds give back
    # what lies behind the window
    assert work.resident_token_bytes(CFG, 2048) == 16384
    assert work.resident_token_bytes(CFG, 8192) == 4096 + 12288 * 0.5
    attn = work.gqa_attention(CFG, 81920.0)
    assert attn["bytes"] == 81920 * 2048 and attn["flops"] == 4 * 28 * 128 * 81920
    assert work.least_seconds(attn, PEAK) == pytest.approx(attn["bytes"] / 819e9)
