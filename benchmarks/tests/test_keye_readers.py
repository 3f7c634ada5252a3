"""The readers ``keye-serve-shared-long-context`` adds, on recorded registry
deltas and a recorded reduction: a number where their series are there,
None where they are not (a parent commit without the counters or the
scopes, a run without a trace, another tower's configuration), and the
operation counts behind them against the configuration's own arithmetic."""

import pytest

import toy  # noqa: F401  (puts benchmarks/ on the path)
from harness import keye_work as work, loading, registry
from harness.peaks import PEAKS

CFG = loading.load_json("configs", "keye-vl2-30b-a3b-l6.json")
PEAK = PEAKS["TPU v5 lite"]


def _value(name, value, **labels):
    return (name, tuple(sorted(labels.items()))), {"value": value}


# 100 decode chunks of 8 steps, 40 live rows of 66,000 tokens: a layer's
# indexer scores 2.64 M keys a step and its attention fetches 40 x 2,048
# rows; 320 pairs a layer hit 110 of the 128 experts
STEPS, LAYERS = 800.0, 6
SCORED, SELECTED, PAIRS, HIT = 40 * 66000.0, 40 * 2048.0, 320.0, 110.0
RECORDED = dict([
    _value("znicz_serve_decode_steps_total", STEPS),
    _value("znicz_serve_sparse_keys_scored_total", STEPS * SCORED, phase="decode"),
    _value("znicz_serve_sparse_keys_selected_total", STEPS * SELECTED, phase="decode"),
    _value("znicz_serve_decode_cached_rows_total", STEPS * SELECTED, kind="global"),
    _value("znicz_serve_moe_layer_steps_total", STEPS * LAYERS, phase="decode"),
    _value("znicz_serve_moe_pairs_total", STEPS * LAYERS * PAIRS, phase="decode"),
    _value("znicz_serve_moe_idle_experts_total", STEPS * LAYERS * (128 - HIT), phase="decode"),
    _value("znicz_serve_prefix_cached_tokens_total", 90 * 65536.0),
    _value("znicz_serve_prompt_tokens_total", 90 * 65920.0),
])
ROOFLINES = (
    "dsa.gqa_indexer_decode_roofline_pct", "dsa.kept_gqa_attn_decode_roofline_pct",
    "moe.silu_experts_roofline_pct",
)
TRACED = ROOFLINES + ("dsa.select_decode_ms_per_step",)
SCOPES = {"dsa_indexer": 0.06, "dsa_select": 0.03, "gqa_sparse": 0.05,
          "moe_experts": 0.08}


def _obs(series=RECORDED, scoped="whole", trace=True, cfg=CFG):
    if scoped == "whole":
        scoped = {
            "jit__paged_decode_chunk": {
                "whole_executions": 1, "steps": 8, "device_s": 0.25,
                "scopes": dict(SCOPES),
            },
        }
    delta = registry.Delta({}, series)
    return {
        "registry": delta, "traced_registry": delta if trace else None,
        "config": cfg, "peaks": PEAK,
        "decode_program": "jit__paged_decode_chunk",
        "prefill_program": "jit__paged_prefill_prog",
        "trace": {"programs": {}} if trace else None,
        "scoped": scoped if trace else None,
    }


def _read(metric, obs):
    return loading.load_module("layer_metrics", metric).read(obs)


def test_the_prefix_hit_share_reads_the_windows_counters():
    got = _read("cache.prefix_hit_pct_of_prompt_tokens", _obs())
    assert got == pytest.approx(100.0 * 65536 / 65920)
    assert _read("cache.prefix_hit_pct_of_prompt_tokens", _obs(series={})) is None
    # a parent commit counts cached tokens but not the prompts'
    older = {k: v for k, v in RECORDED.items() if "prompt_tokens" not in k[0]}
    assert _read("cache.prefix_hit_pct_of_prompt_tokens", _obs(series=older)) is None


def test_scope_rooflines_read_the_marked_operations_of_the_decode_program():
    for metric, job, scope in (
        ("dsa.gqa_indexer_decode_roofline_pct", work.index_scores(CFG, SCORED),
         "dsa_indexer"),
        ("dsa.kept_gqa_attn_decode_roofline_pct",
         work.kept_attention(CFG, SELECTED), "gqa_sparse"),
        ("moe.silu_experts_roofline_pct", work.experts_product(CFG, HIT, PAIRS),
         "moe_experts"),
    ):
        want = 100.0 * work.least_seconds(job, PEAK) * LAYERS * 8 / SCOPES[scope]
        got = _read(metric, _obs())
        assert got == pytest.approx(want) and 0 < got < 100, metric


def test_the_selection_reads_milliseconds_a_step():
    assert _read("dsa.select_decode_ms_per_step", _obs()) == pytest.approx(
        1e3 * 0.03 / 8
    )


def test_a_trace_that_holds_only_stubs_reads_nothing():
    stubs = {
        "jit__paged_decode_chunk": {
            "whole_executions": 0, "steps": 0, "device_s": 0.0, "scopes": {},
        }
    }
    for metric in TRACED:
        assert _read(metric, _obs(scoped=stubs)) is None


@pytest.mark.parametrize("metric", TRACED)
def test_a_program_without_the_counters_or_scopes_reads_nothing(metric):
    """What the parent commit gives: the engine's older series and a trace
    whose operations carry none of this tower's scopes."""
    older = {k: v for k, v in RECORDED.items() if "sparse" not in k[0]}
    unmarked = {
        "jit__paged_decode_chunk": {
            "whole_executions": 3, "steps": 8, "device_s": 0.1, "scopes": {},
        }
    }
    assert _read(metric, _obs(series=older)) is None
    assert _read(metric, _obs(scoped=unmarked)) is None


@pytest.mark.parametrize("metric", TRACED)
def test_another_towers_configuration_reads_nothing(metric):
    other = loading.load_json("configs", "dots3-ep16-l5.json")
    assert _read(metric, _obs(cfg=other)) is None


@pytest.mark.parametrize("metric", TRACED)
def test_a_run_without_a_trace_reads_no_device_metric(metric):
    assert _read(metric, _obs(trace=False)) is None


def test_operation_counts_follow_the_configuration():
    idx = work.index_scores(CFG, 1000.0)
    assert idx["bytes"] == 1000 * 128 and idx["flops"] == 1000 * 2 * 16 * 64
    kept = work.kept_attention(CFG, 2048.0)
    assert kept["bytes"] == 2048 * 2048
    assert kept["flops"] == 2048 * 2 * 2 * 32 * 128
    experts = work.experts_product(CFG, 128.0, 0.0)
    assert experts["bytes"] == 2 * 128 * 3 * 2048 * 768  # a layer's 1.21 GB
    assert work.least_seconds(idx, PEAK) == pytest.approx(idx["bytes"] / 819e9)
