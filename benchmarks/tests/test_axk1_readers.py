"""The readers ``axk1-serve-shared-doc`` adds, on recorded registry
deltas and a recorded reduction: a number where their series are there,
None where they are not (a parent commit without the counters, a run
without a trace), and the operation counts behind them against the
configuration's own arithmetic."""

import pytest

import toy  # noqa: F401  (puts benchmarks/ on the path)
from harness import axk1_work, loading, registry
from harness.peaks import PEAKS

CFG = loading.load_json("configs", "axk1-ep16.json")
PEAK = PEAKS["TPU v5 lite"]


def _value(name, value, **labels):
    return (name, tuple(sorted(labels.items()))), {"value": value}


# a window of 100 decode chunks of 8 steps at 128 slots and a 96-block
# window: 800 steps, 4000 routed layer-steps; 5.3 pairs an expert
STEPS, CHUNKS, LAYER_STEPS = 800.0, 100.0, 4000.0
RECORDED = dict([
    _value("znicz_serve_decode_steps_total", STEPS),
    _value("znicz_serve_decode_chunks_total", CHUNKS, window="96"),
    _value("znicz_serve_decode_gathered_tokens_total", STEPS * 128 * 96 * 128),
    _value("znicz_serve_moe_layer_steps_total", LAYER_STEPS, phase="decode"),
    _value("znicz_serve_moe_layer_steps_total", 900.0, phase="prefill"),
    _value("znicz_serve_moe_busiest_pairs_total", LAYER_STEPS * 11.0, phase="decode"),
    _value("znicz_serve_moe_idle_experts_total", LAYER_STEPS * 0.5, phase="decode"),
] + [
    _value("znicz_serve_moe_pairs_total", LAYER_STEPS * 64.0 / 12, phase="decode", expert=str(e))
    for e in range(12)
] + [
    _value("znicz_serve_moe_pairs_total", 7.0, phase="prefill", expert="0"),
])


def _obs(series=RECORDED, scoped="whole", trace=True):
    """What the driver hands the readers.  ``scoped``: the decode program
    with one whole execution of 8 steps in the trace (0.6 s on the
    device, most of it in the marked attention), or a table of its own."""
    if scoped == "whole":
        scoped = {
            "jit__paged_decode_chunk": {
                "whole_executions": 1, "steps": 8, "device_s": 0.6,
                "scopes": {"moe_experts": 0.08, "mla_absorbed": 0.24},
            },
            "jit__paged_prefill_prog": {
                "whole_executions": 9, "steps": 9, "device_s": 0.3,
                "scopes": {"moe_experts": 9.9, "mla_materialised": 9.9},
            },
        }
    return {
        "registry": registry.Delta({}, series), "config": CFG, "peaks": PEAK,
        "decode_program": "jit__paged_decode_chunk",
        "trace": {"programs": {}} if trace else None,
        "scoped": scoped if trace else None,
    }


def _read(metric, obs):
    return loading.load_module("layer_metrics", metric).read(obs)


def test_counter_readers_on_a_recorded_delta():
    assert _read("moe.pairs_per_held_expert", _obs()) == pytest.approx(64.0 / 12)
    assert _read("moe.load_max_over_mean", _obs()) == pytest.approx(11.0 / (64.0 / 12))


def test_hbm_roofline_reads_bytes_over_whole_executions():
    # 8 steps in the one whole execution; 11.5 of 12 experts hit a layer
    step_bytes = axk1_work.decode_step_bytes(CFG, 11.5, 128 * 96 * 128)
    want = 100.0 * step_bytes / 819e9 * 8 / 0.6
    assert _read("decode.hbm_roofline_pct", _obs()) == pytest.approx(want)
    assert 0 < want < 100


def test_scope_rooflines_read_the_marked_operations_of_the_decode_program():
    experts = axk1_work.experts_product(CFG, 11.5, 64.0)
    want = 100.0 * axk1_work.least_seconds(experts, PEAK) * 5 * 8 / 0.08
    assert _read("moe.experts_roofline_pct", _obs()) == pytest.approx(want)
    attn = axk1_work.absorbed_attention(CFG, 128, 96 * 128)
    want = 100.0 * axk1_work.least_seconds(attn, PEAK) * 6 * 8 / 0.24
    assert _read("mla.decode_attn_roofline_pct", _obs()) == pytest.approx(want)


def test_a_trace_that_holds_only_stubs_of_the_decode_program_reads_nothing():
    stubs = {"jit__paged_decode_chunk": {
        "whole_executions": 0, "steps": 0, "device_s": 0.0, "scopes": {}}}
    for metric in ALL[2:]:
        assert _read(metric, _obs(scoped=stubs)) is None


ALL = (
    "moe.pairs_per_held_expert", "moe.load_max_over_mean",
    "decode.hbm_roofline_pct", "moe.experts_roofline_pct",
    "mla.decode_attn_roofline_pct",
)


@pytest.mark.parametrize("metric", ALL)
def test_a_program_without_the_counters_reads_nothing(metric):
    """What the parent commit gives: the engine's older series, none of
    the new ones, and a trace whose operations carry no marked scope."""
    older = {k: v for k, v in RECORDED.items() if "moe" not in k[0]}
    assert _read(metric, _obs(series=older, scoped={})) is None


@pytest.mark.parametrize("metric", ALL[2:])
def test_a_run_without_a_trace_reads_no_device_metric(metric):
    assert _read(metric, _obs(trace=False)) is None


def test_operation_counts_follow_the_configuration():
    held = CFG["parameters_held"]
    # every weight but the embedding and the routed experts, read once
    always = axk1_work.always_read_params(CFG)
    assert always == (
        held["total"] - CFG["vocab_size"] * CFG["hidden_size"]
        - 5 * 12 * axk1_work.expert_params(CFG)
    )
    assert axk1_work.expert_params(CFG) == 3 * 7168 * 2048
    assert axk1_work.cache_row_bytes(CFG) == 576 * 2
    full = axk1_work.decode_step_bytes(CFG, 12, 128 * 9216)
    # the issue's count: 8.3 GB of weights (less the embedding's 0.29)
    # and up to 7.9 GB of latent rows
    assert full == pytest.approx(8.04e9 + 6 * 128 * 9216 * 1152, rel=0.01)
    attn = axk1_work.absorbed_attention(CFG, 128, 12288)
    assert attn["bytes"] == pytest.approx(128 * 12288 * 1152 + 2 * 512 * 64 * 256)
    assert axk1_work.least_seconds(attn, PEAK) == pytest.approx(attn["bytes"] / 819e9)
