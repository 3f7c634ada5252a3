"""``attn.global_fetched_pct_of_attended`` (PR 47) on recorded registry
deltas: the share where both counters are there, None where the program has
no ``znicz_serve_decode_attended_rows_total`` (a parent commit), the run
traced nothing or the configuration is another tower's."""

import pytest

import toy  # noqa: F401  (puts benchmarks/ on the path)
from harness import loading, registry

CFG = loading.load_json("configs", "laguna-xs2-stage1.json")
METRIC = "attn.global_fetched_pct_of_attended"


def _value(name, value, **labels):
    return (name, tuple(sorted(labels.items()))), {"value": value}


# 800 steps of 15 live rows of 14,080 keys, 12,288 of them shared: two tiles
# read the run once each and every row its own 1,792
ATTENDED = 800 * 15 * 14080.0
FETCHED = 800 * (2 * 12288.0 + 15 * 1792.0)
COUNTERS = [
    _value("znicz_serve_decode_cached_rows_total", FETCHED, kind="global"),
    _value("znicz_serve_decode_cached_rows_total", 1e6, kind="window"),
    _value("znicz_serve_decode_attended_rows_total", ATTENDED, kind="global"),
    _value("znicz_serve_decode_attended_rows_total", 1e6, kind="window"),
]


def _read(series, cfg=CFG, trace=True):
    delta = registry.Delta({}, dict(series))
    obs = {
        "registry": delta, "traced_registry": delta if trace else None,
        "config": cfg,
    }
    return loading.load_module("layer_metrics", METRIC).read(obs)


def test_the_share_is_fetched_over_attended_of_the_global_kind():
    assert _read(COUNTERS) == pytest.approx(100.0 * FETCHED / ATTENDED)
    assert 24 < _read(COUNTERS) < 25
    # nothing shared: every row fetched for itself
    alone = COUNTERS[1:] + [
        _value("znicz_serve_decode_cached_rows_total", ATTENDED, kind="global")
    ]
    assert _read(alone) == 100.0


@pytest.mark.parametrize(
    "series, cfg, trace",
    [
        (COUNTERS[:2], CFG, True), (COUNTERS[2:], CFG, True),
        (COUNTERS, CFG, False), (COUNTERS, dict(CFG, model_type="keye"), True),
        (COUNTERS, None, True),
    ],
    ids=["a_parent_without_the_counter", "no_rows_fetched", "no_trace",
         "another_tower", "no_configuration"],
)
def test_it_reads_nothing_where_there_is_nothing_to_read(series, cfg, trace):
    assert _read(series, cfg, trace) is None
