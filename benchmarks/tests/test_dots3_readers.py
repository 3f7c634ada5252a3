"""The readers ``dots3-serve-long-docs`` adds, on recorded registry deltas
and a recorded reduction: a number where their series are there, None
where they are not (a parent commit without the counters or the scopes, a
run without a trace, another tower's configuration), and the operation
counts behind them against the configuration's own arithmetic."""

import pytest

import toy  # noqa: F401  (puts benchmarks/ on the path)
from harness import dots3_work as work, loading, registry
from harness.peaks import PEAKS

CFG = loading.load_json("configs", "dots3-ep16-l5.json")
PEAK = PEAKS["TPU v5 lite"]


def _value(name, value, **labels):
    return (name, tuple(sorted(labels.items()))), {"value": value}


# 100 decode chunks of 8 steps, 6 live rows of 10,240 tokens: a full
# layer's indexer scores 61,440 keys a step and its attention reads 6 x
# 2,048 rows; a window layer reads 6 rows' 5 blocks in place
STEPS = 800.0
SCORED, SELECTED, WINDOW_ROWS = 6 * 10240.0, 6 * 2048.0, 6 * 640.0
RECORDED = dict([
    _value("znicz_serve_decode_steps_total", STEPS),
    _value("znicz_serve_sparse_keys_scored_total", STEPS * SCORED, phase="decode"),
    _value("znicz_serve_sparse_keys_selected_total", STEPS * SELECTED, phase="decode"),
    _value("znicz_serve_sparse_keys_scored_total", 9e9, phase="prefill"),
    _value("znicz_serve_sparse_keys_selected_total", 9e8, phase="prefill"),
    _value("znicz_serve_decode_cached_rows_total", STEPS * SELECTED, kind="global"),
    _value("znicz_serve_decode_cached_rows_total", STEPS * WINDOW_ROWS, kind="window"),
])
ROOFLINES = (
    "dsa.indexer_decode_roofline_pct", "dsa.sparse_attn_decode_roofline_pct",
    "mla.window_decode_roofline_pct",
)
TRACED = ROOFLINES + ("dsa.prefill_full_layer_ms_per_chunk",)


def _obs(series=RECORDED, scoped="whole", trace=True, cfg=CFG):
    """What the driver hands the readers.  ``scoped``: the decode program
    with one whole execution of 8 steps and the prefill program with 20 in
    the trace, or a table of its own."""
    if scoped == "whole":
        scoped = {
            "jit__paged_decode_chunk": {
                "whole_executions": 1, "steps": 8, "device_s": 0.05,
                "scopes": {"dsa_indexer": 0.004, "dsa_select": 0.003,
                           "mla_sparse": 0.006, "mla_window": 0.002},
            },
            "jit__paged_prefill_prog": {
                "whole_executions": 20, "steps": 20, "device_s": 0.3,
                "scopes": {"dsa_indexer": 0.02, "dsa_select": 0.05,
                           "mla_sparse": 0.03, "mla_window": 0.01},
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


def test_the_selected_share_reads_the_windows_decode_counters():
    assert _read("dsa.selected_share_of_keys", _obs()) == pytest.approx(20.0)
    assert _read("dsa.selected_share_of_keys", _obs(series={})) is None


def test_scope_rooflines_read_the_marked_operations_of_the_decode_program():
    for metric, job, layers, seconds in (
        ("dsa.indexer_decode_roofline_pct", work.index_scores(CFG, SCORED), 2, 0.004),
        ("dsa.sparse_attn_decode_roofline_pct",
         work.sparse_attention(CFG, SELECTED), 2, 0.006),
        ("mla.window_decode_roofline_pct",
         work.window_attention(CFG, WINDOW_ROWS), 3, 0.002),
    ):
        want = 100.0 * work.least_seconds(job, PEAK) * layers * 8 / seconds
        got = _read(metric, _obs())
        assert got == pytest.approx(want) and 0 < got < 100, metric


def test_the_full_layers_prefill_time_is_a_chunks():
    got = _read("dsa.prefill_full_layer_ms_per_chunk", _obs())
    assert got == pytest.approx(1e3 * (0.02 + 0.05 + 0.03) / 20)


def test_a_trace_that_holds_only_stubs_reads_nothing():
    stubs = {
        name: {"whole_executions": 0, "steps": 0, "device_s": 0.0, "scopes": {}}
        for name in ("jit__paged_decode_chunk", "jit__paged_prefill_prog")
    }
    for metric in TRACED:
        assert _read(metric, _obs(scoped=stubs)) is None


@pytest.mark.parametrize("metric", TRACED + ("dsa.selected_share_of_keys",))
def test_a_program_without_the_counters_or_scopes_reads_nothing(metric):
    """What the parent commit gives: the engine's older series, none of
    the new ones, and a trace whose operations carry no marked scope."""
    older = {k: v for k, v in RECORDED.items() if "sparse" not in k[0]}
    unmarked = {
        name: {"whole_executions": 3, "steps": 8, "device_s": 0.1, "scopes": {}}
        for name in ("jit__paged_decode_chunk", "jit__paged_prefill_prog")
    }
    assert _read(metric, _obs(series=older, scoped=unmarked)) is None


@pytest.mark.parametrize("metric", ROOFLINES)
def test_another_towers_configuration_reads_nothing(metric):
    other = loading.load_json("configs", "axk1-ep16.json")
    assert _read(metric, _obs(cfg=other)) is None


@pytest.mark.parametrize("metric", TRACED)
def test_a_run_without_a_trace_reads_no_device_metric(metric):
    assert _read(metric, _obs(trace=False)) is None


def test_operation_counts_follow_the_configuration():
    assert work.layers_of(CFG) == {"global": 2, "window": 3}
    idx = work.index_scores(CFG, 1000.0)
    assert idx["bytes"] == 1000 * 256 and idx["flops"] == 1000 * 2 * 64 * 128
    sparse = work.sparse_attention(CFG, 2048.0)
    # one query's 2,048 rows of 1,152 B, and wk_b and wv_b once
    assert sparse["bytes"] == 2048 * 1152 + 2 * 512 * 128 * 256
    assert sparse["flops"] == (
        2 * 128 * 512 * 256 + 2 * 128 * 2048 * (576 + 512)
    )
    window = work.window_attention(CFG, 513.0)
    assert window["bytes"] == 513 * 2176 + 2 * 1024 * 64 * 320
    assert window["flops"] == 2 * 64 * 1024 * 320 + 2 * 64 * 513 * (1088 + 1024)
    assert work.least_seconds(idx, PEAK) == pytest.approx(idx["bytes"] / 819e9)
