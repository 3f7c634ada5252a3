"""The ``serve_sparse_latent`` driver at toy size on the CPU: a sound run is
correct, scores and selects keys and gives window blocks back; each of the
four controls is not correct (it fails a limit on the gaps); a token
altered where it is produced is not; and the whole command prints a result
line with the cell's metrics."""

import json

import numpy as np
import pytest

import toy_dots3
from harness import loading

serve = loading.load_module("drivers", "serve_sparse_latent")
VOCAB = toy_dots3.config()["vocab_size"]


@pytest.fixture(scope="module")
def window():
    """One warm toy server, one window: what the reference then reads."""
    cfg, mix = toy_dots3.config(), toy_dots3.workload()["traffic"]
    server = serve.Server(cfg, 77, mix["deadline_s"])
    try:
        server.warm(np.random.default_rng(1), 20)
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
    assert [r["name"] for r in checks.rows] == [
        "answers_cut_short", "served_logit_gap_widest", "served_logit_gap_mean",
        "selected_keys_not_shared_mean",
    ]
    delta = measured["delta"]
    assert delta.value("znicz_serve_window_blocks_released_total") > 0
    assert not delta.value("znicz_serve_prefix_hits_total")
    for phase in ("prefill", "decode"):
        scored = delta.value("znicz_serve_sparse_keys_scored_total", phase=phase)
        kept = delta.value("znicz_serve_sparse_keys_selected_total", phase=phase)
        assert 0 < kept < scored
    for kind in ("global", "window"):
        assert delta.value("znicz_serve_decode_cached_rows_total", kind=kind) > 0


@pytest.mark.parametrize("control", sorted(serve.CONTROLS))
def test_a_control_is_not_correct(window, control):
    """One step of precision down in every product, every key attended,
    the most recent keys instead of the best-scored, half as many kept."""
    cfg, mix, w, summary, _ = window
    checks = serve.decide_correct(
        cfg, w, summary["good"], 77, mix, control=serve.CONTROLS[control]
    )
    assert not checks.correct, checks.rows
    assert checks.rows[0]["ok"]  # no answer cut short: a limit on the gaps failed
    assert not (checks.rows[1]["ok"] and checks.rows[2]["ok"])


def test_the_weights_count_what_the_configuration_states():
    from harness import dots3_weights

    cfg = loading.load_json("configs", "dots3-ep16-l5.json")
    held = cfg["parameters_held"]
    assert dots3_weights.n_parameters(cfg) == held["total"] == 2577137664
    assert held["bytes"] == 2 * held["total"]
    assert held["layer_0"] == held["full_attention_a_layer"] + held["dense_ffn"]
    assert held["layer_1"] == (
        held["full_attention_a_layer"] + held["router_a_layer"]
        + 17 * held["an_expert"]
    )
    assert held["a_window_layer"] == (
        held["window_attention_a_layer"] + held["router_a_layer"]
        + 17 * held["an_expert"]
    )
    assert held["total"] == (
        held["layer_0"] + held["layer_1"] + 3 * held["a_window_layer"]
        + held["embedding_and_head"]
    )


def test_the_configuration_keeps_the_published_widths():
    """Every number of the catalog's row is in the file under its key,
    but for the three keys listed as reduced."""
    cfg = loading.load_json("configs", "dots3-ep16-l5.json")
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert cfg["published"] == {
        "num_hidden_layers": 46, "n_routed_experts": 256, "vocab_size": 152064
    }
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"], cfg["vocab_size"]) == (
        5, 16, 152064 // 8
    )
    assert len(cfg["layer_types"]) == 46
    assert cfg["layer_types"][:5] == (
        ["full_attention"] * 2 + ["sliding_attention"] * 3
    )
    assert (cfg["hidden_size"], cfg["index_topk"], cfg["sliding_window_size"]) == (
        5120, 2048, 513
    )
    dep = cfg["deployment"]
    assert dep["first_expert"] == dep["chip_index"] * cfg["n_routed_experts"]
    assert dep["chips_per_layer"] * cfg["n_routed_experts"] == 256


@pytest.fixture
def toy_benchmark(monkeypatch, tmp_path):
    import run as run_module

    cfg = toy_dots3.config()
    (tmp_path / "toy-dots3.json").write_text(json.dumps(cfg))
    real = loading.benchmark_json()
    bench = dict(
        real,
        configs=[{"name": "toy-dots3", "file": str(tmp_path / "toy-dots3.json")}],
        workloads=[{"name": "toy-dots3-serve", "config": "toy-dots3",
                    "traffic": "long-docs", "chips": 1}],
    )
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in metric:
            metric["workloads"] = [
                "toy-dots3-serve" if w.startswith("dots3") else w
                for w in metric["workloads"]
            ]
    cell = toy_dots3.workload()
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
        ["--workload", "toy-dots3-serve", "--seed", "3000000005",
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
        ["--workload", "toy-dots3-serve", "--seed", "6", "--seconds",
         "2", "--trace", "0"], require_chip=False,
    )
    line = _last_line(capsys)
    assert line["correct"] is False, line
