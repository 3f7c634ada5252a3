"""The ``serve_window_moe`` driver at toy size on the CPU: a sound run is
correct and gives window blocks back, both controls are not correct (each
fails a limit on the gaps), a token altered where it is produced is not,
and the whole command prints a result line with the cell's metrics and,
traced, its counter readers."""

import json

import numpy as np
import pytest

import toy_smallthinker
from harness import loading
from harness.checks import float8

serve = loading.load_module("drivers", "serve_window_moe")
VOCAB = toy_smallthinker.config()["vocab_size"]


@pytest.fixture(scope="module")
def window():
    """One warm toy server, one window: what the reference then reads."""
    cfg, mix = toy_smallthinker.config(), toy_smallthinker.workload()["traffic"]
    server = serve.Server(cfg, 77, mix["deadline_s"])
    try:
        server.warm(np.random.default_rng(1), 10)
        measured = serve.measure(server, mix, 77, 2.0)
    finally:
        server.close()
    summary = serve.summarise(measured, 2.0, mix["deadline_s"])
    return cfg, mix, server.weights, summary, measured


def test_the_unbroken_path_is_correct(window):
    cfg, mix, w, summary, measured = window
    assert summary["failed"] == 0 and len(summary["good"]) >= 8
    checks = serve.decide_correct(cfg, w, summary["good"], 77, mix)
    assert checks.correct, checks.rows
    delta = measured["delta"]
    # rows crossed the 32-token window and gave blocks back; no prefix hit
    assert delta.value("znicz_serve_window_blocks_released_total") > 0
    assert not delta.value("znicz_serve_prefix_hits_total")
    for kind in ("global", "window"):
        assert delta.value("znicz_serve_decode_cached_rows_total", kind=kind) > 0


@pytest.mark.parametrize(
    "control", [{"control": float8}, {"cache_control": float8}],
    ids=["float8_products", "float8_cache"],
)
def test_a_control_one_step_of_precision_down_is_not_correct(window, control):
    cfg, mix, w, summary, _ = window
    checks = serve.decide_correct(cfg, w, summary["good"], 77, mix, **control)
    assert not checks.correct, checks.rows
    assert checks.rows[0]["ok"]  # no answer cut short: a limit on the gaps failed


def test_the_weights_count_what_the_configuration_states():
    from harness import smallthinker_weights

    cfg = loading.load_json("configs", "smallthinker-21b-l8.json")
    held = cfg["parameters_held"]
    assert smallthinker_weights.n_parameters(cfg) == held["total"] == 3966894080
    assert held["bytes"] == 2 * held["total"]
    assert held["a_layer"] == (
        held["attention_a_layer"] + held["router_a_layer"] + held["experts_a_layer"]
    )


def test_the_capture_keeps_the_registry_s_share_of_the_traced_seconds(monkeypatch):
    """The roofline readers take their counters from the seconds the trace
    covers: the capture reads the registry as the trace starts and stops."""
    from harness import scoped_trace
    from znicz_tpu import observability

    monkeypatch.setattr(scoped_trace.ScopedCapture, "start", lambda self: None)
    monkeypatch.setattr(scoped_trace.ScopedCapture, "stop", lambda self: None)
    steps = observability.counter(
        "znicz_serve_decode_steps_total", "token steps (see the engine)"
    )
    capture = serve.TracedCapture(serve.SCOPES)
    assert capture.traced_registry is None
    steps.inc(5)  # before the trace: not its share
    capture.start()
    steps.inc(3)
    capture.stop()
    steps.inc(7)
    assert capture.traced_registry.value("znicz_serve_decode_steps_total") == 3


@pytest.fixture
def toy_benchmark(monkeypatch, tmp_path):
    import run as run_module

    cfg = toy_smallthinker.config()
    (tmp_path / "toy-smallthinker.json").write_text(json.dumps(cfg))
    real = loading.benchmark_json()
    bench = dict(
        real,
        configs=[{"name": "toy-smallthinker",
                  "file": str(tmp_path / "toy-smallthinker.json")}],
        workloads=[{"name": "toy-smallthinker-serve", "config": "toy-smallthinker",
                    "traffic": "mixed-lengths", "chips": 1}],
    )
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in metric:
            metric["workloads"] = [
                "toy-smallthinker-serve" if w.startswith("smallthinker") else w
                for w in metric["workloads"]
            ]
    cell = toy_smallthinker.workload()
    monkeypatch.setattr(loading, "benchmark_json", lambda: bench)
    monkeypatch.setattr(
        loading, "load_json",
        lambda *rel: cell if rel[0] == "workloads" else None,
    )
    return run_module


def _last_line(capsys) -> dict:
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_the_command_prints_the_cell_s_result_line(toy_benchmark, capsys):
    rc = toy_benchmark.main(
        ["--workload", "toy-smallthinker-serve", "--seed", "3000000005",
         "--seconds", "2", "--trace", "0"], require_chip=False,
    )
    line = _last_line(capsys)
    assert rc == 0 and line["correct"] is True, line
    assert line["failed"] == 0 and line["attempted"] > 0
    assert {"tpot_p95_ms", "tokens_per_s", "setup_s"} <= set(line["metrics"])


def test_a_token_altered_where_it_is_produced_is_not_correct(
    toy_benchmark, capsys, monkeypatch
):
    from znicz_tpu.services import engine

    real = engine._paged_decode_chunk

    def altered(*args, **kwargs):
        pools, tok, pos, done, remaining, out, steps, load = real(*args, **kwargs)
        return (pools, (tok + 1) % VOCAB, pos, done, remaining,
                (out + 1) % VOCAB, steps, load)

    altered._cache_size = real._cache_size
    monkeypatch.setattr(engine, "_paged_decode_chunk", altered)
    toy_benchmark.main(
        ["--workload", "toy-smallthinker-serve", "--seed", "6", "--seconds",
         "2", "--trace", "0"], require_chip=False,
    )
    line = _last_line(capsys)
    assert line["correct"] is False, line
