"""The readers ``laguna-serve-agent-turns`` adds, on recorded registry deltas
and a recorded reduction: a number where their series are there, None where
they are not (a parent commit without the counters or the scopes, a run
without a trace, another tower's configuration), and the operation counts
behind them against the configuration's own arithmetic."""

import pytest

import toy  # noqa: F401  (puts benchmarks/ on the path)
from harness import laguna_work as work, loading, registry
from harness.peaks import PEAKS

CFG = loading.load_json("configs", "laguna-xs2-stage1.json")
PEAK = PEAKS["TPU v5 lite"]


def _value(name, value, **labels):
    return (name, tuple(sorted(labels.items()))), {"value": value}


# 200 decode chunks of 4 steps, 24 live rows of 14,000 tokens: a global layer
# reads 24 x 14,080 rows a step, a window layer 24 x 640; 192 pairs a routed
# layer hit 135 of the 256 experts; 90 requests mapped 96 global blocks and
# the window kind's last 4
STEPS, ROUTED = 800.0, 6
ROWS = {"global": 24 * 14080.0, "window": 24 * 640.0}
PAIRS, HIT = 192.0, 135.0
RECORDED = dict([
    _value("znicz_serve_decode_steps_total", STEPS),
    _value("znicz_serve_decode_cached_rows_total", STEPS * ROWS["global"], kind="global"),
    _value("znicz_serve_decode_cached_rows_total", STEPS * ROWS["window"], kind="window"),
    _value("znicz_serve_moe_layer_steps_total", STEPS * ROUTED, phase="decode"),
    _value("znicz_serve_moe_pairs_total", STEPS * ROUTED * PAIRS, phase="decode"),
    _value("znicz_serve_moe_idle_experts_total", STEPS * ROUTED * (256 - HIT), phase="decode"),
    _value("znicz_serve_prefix_blocks_mapped_total", 90 * 96.0, kind="global"),
    _value("znicz_serve_prefix_blocks_mapped_total", 90 * 4.0, kind="window"),
    _value("znicz_serve_prefix_hit_requests_total", 90.0),
])
SCOPES = {"attn_global": 0.016, "attn_window": 0.004, "moe_experts": 0.03}
ROOFLINES = (
    "attn.gated_window_decode_roofline_pct", "attn.gated_global_decode_roofline_pct",
    "moe.small_experts_roofline_pct", "decode.step_hbm_roofline_pct",
)
COUNTED = ("moe.small_experts_hit_per_layer", "cache.prefix_window_blocks_per_hit")


def _obs(series=RECORDED, scoped="whole", trace=True, cfg=CFG):
    if scoped == "whole":
        scoped = {
            "jit__paged_decode_chunk": {
                "whole_executions": 1, "steps": 4, "device_s": 0.06,
                "scopes": dict(SCOPES),
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


def test_the_work_is_the_issue_s_arithmetic():
    assert work.gqa_attention(CFG, "window", 1.0) == {
        "bytes": 4096.0, "flops": 4.0 * 64 * 128,
    }
    assert work.gqa_attention(CFG, "global", 1.0)["flops"] == 4.0 * 48 * 128
    assert work.expert_params(CFG) * work.BYTES == 6291456  # 6.29 MB an expert
    step = work.decode_step_bytes(CFG, HIT, ROWS)
    assert step == pytest.approx(
        2 * work.always_read_params(CFG) + 6 * HIT * 6291456
        + (2 * ROWS["global"] + 5 * ROWS["window"]) * 4096
    )


def test_scope_rooflines_read_the_marked_operations_of_the_decode_program():
    for metric, job, scope, layers in (
        ("attn.gated_window_decode_roofline_pct",
         work.gqa_attention(CFG, "window", ROWS["window"]), "attn_window", 5),
        ("attn.gated_global_decode_roofline_pct",
         work.gqa_attention(CFG, "global", ROWS["global"]), "attn_global", 2),
        ("moe.small_experts_roofline_pct", work.experts_product(CFG, HIT, PAIRS),
         "moe_experts", 6),
    ):
        want = 100.0 * work.least_seconds(job, PEAK) * layers * 4 / SCOPES[scope]
        got = _read(metric, _obs())
        assert got == pytest.approx(want) and 0 < got < 100, metric
    whole = _read("decode.step_hbm_roofline_pct", _obs())
    assert whole == pytest.approx(
        100.0 * work.decode_step_bytes(CFG, HIT, ROWS) / PEAK["hbm_bytes_per_s"] * 4 / 0.06
    ) and 0 < whole < 100


def test_the_counted_metrics_read_the_windows_counters():
    assert _read("moe.small_experts_hit_per_layer", _obs()) == pytest.approx(HIT)
    assert _read("cache.prefix_window_blocks_per_hit", _obs()) == pytest.approx(4.0)
    # a match whose window holder was lost maps nothing in the window kind
    lost = dict(RECORDED)
    lost.update([_value("znicz_serve_prefix_blocks_mapped_total", 45 * 4.0, kind="window")])
    assert _read("cache.prefix_window_blocks_per_hit", _obs(series=lost)) == 2.0


@pytest.mark.parametrize("metric", ROOFLINES + COUNTED)
def test_a_program_without_the_counters_or_scopes_reads_nothing(metric):
    """What the parent commit gives (no mapped-blocks counters), a run that
    traced nothing, a trace whose operations carry none of the scopes or
    holds only stubs of the decode program, and another tower's
    configuration."""
    assert _read(metric, _obs(series={})) is None
    other = {"model_type": "other", "num_experts": 256}
    if metric != "cache.prefix_window_blocks_per_hit":
        assert _read(metric, _obs(cfg=other)) is None
    if metric in COUNTED:
        return
    assert _read(metric, _obs(trace=False)) is None
    stubs = {
        "jit__paged_decode_chunk": {
            "whole_executions": 0, "steps": 0, "device_s": 0.0, "scopes": {},
        }
    }
    assert _read(metric, _obs(scoped=stubs)) is None
    if metric != "decode.step_hbm_roofline_pct":
        unmarked = {
            "jit__paged_decode_chunk": {
                "whole_executions": 3, "steps": 4, "device_s": 0.1, "scopes": {},
            }
        }
        assert _read(metric, _obs(scoped=unmarked)) is None
