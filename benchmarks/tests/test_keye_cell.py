"""The ``serve_sparse_gqa`` driver at toy size on the CPU: a sound run is
correct, maps the primed prefix from the prefix cache, scores and selects
keys and fetches the kept rows alone; each of the five controls is not
correct (it fails a limit on the gaps); and the whole command prints a
result line with the cell's metrics."""

import json

import numpy as np
import pytest

import toy_keye
from harness import keye_weights, loading

serve = loading.load_module("drivers", "serve_sparse_gqa")
SEED = 77


@pytest.fixture(scope="module")
def window():
    """One warm, primed toy server, one window: what the reference reads."""
    cfg, mix = toy_keye.config(), toy_keye.workload()["traffic"]
    server = serve.Server(cfg, SEED, mix["deadline_s"])
    try:
        server.prime(mix, SEED)
        measured = serve.measure(server, mix, SEED, 2.0)
    finally:
        server.close()
    summary = serve.summarise(measured, 2.0, mix["deadline_s"])
    return cfg, mix, server.weights, summary, measured


def test_the_unbroken_path_is_correct_and_shares_the_prefix(window):
    cfg, mix, w, summary, measured = window
    assert summary["failed"] == 0 and len(summary["good"]) >= 8
    checks = serve.decide_correct(cfg, w, summary["good"], SEED, mix)
    print(checks.rows)
    assert checks.correct, checks.rows
    assert [r["name"] for r in checks.rows] == [
        "answers_cut_short", "served_logit_gap_widest", "served_logit_gap_mean",
        "selected_keys_not_shared_mean",
    ]
    delta = measured["delta"]
    prompts = delta.value("znicz_serve_prompt_tokens_total")
    cached = delta.value("znicz_serve_prefix_cached_tokens_total")
    # every request admitted inside the window maps the prefix's 8 blocks
    # whole (one due at the window's end may be admitted after it closes)
    assert cached % 64 == 0 and cached >= 64 * (len(measured["outcomes"]) - 2)
    assert 0.4 < cached / prompts < 1
    for phase in ("prefill", "decode"):
        scored = delta.value("znicz_serve_sparse_keys_scored_total", phase=phase)
        kept = delta.value("znicz_serve_sparse_keys_selected_total", phase=phase)
        assert 0 < kept < scored
    # a decode step fetches the kept rows and nothing else
    assert delta.value(
        "znicz_serve_decode_cached_rows_total", kind="global"
    ) == delta.value("znicz_serve_sparse_keys_selected_total", phase="decode")


@pytest.mark.parametrize("control", sorted(serve.CONTROLS))
def test_a_control_is_not_correct(window, control):
    """One step of precision down in every product, every key attended,
    the most recent keys instead of the best-scored, half as many kept,
    the shared prefix's indexer keys read as zeros."""
    cfg, mix, w, summary, _ = window
    checks = serve.decide_correct(
        cfg, w, summary["good"], SEED, mix, control=serve.CONTROLS[control]
    )
    print(control, checks.rows)
    assert not checks.correct, checks.rows
    assert checks.rows[0]["ok"]  # no answer cut short: a limit on the gaps failed
    assert not (checks.rows[1]["ok"] and checks.rows[2]["ok"])


def test_the_weights_count_what_the_configuration_states():
    cfg = loading.load_json("configs", "keye-vl2-30b-a3b-l6.json")
    held = cfg["parameters_held"]
    assert keye_weights.n_parameters(cfg) == held["total"] == 4374593536
    assert held["bytes"] == 2 * held["total"]
    assert held["a_layer"] == (
        held["attention_a_layer"] + held["indexer_a_layer"]
        + held["router_a_layer"] + 128 * held["an_expert"]
    )
    assert held["total"] == 6 * held["a_layer"] + held["embedding_and_head"]


def test_the_configuration_keeps_every_published_key():
    """Every key of the catalog's row is in the file at its value, but for
    the one listed as reduced."""
    cfg = loading.load_json("configs", "keye-vl2-30b-a3b-l6.json")
    assert cfg["reduced"] == ["num_hidden_layers"]
    assert cfg["published"] == {"num_hidden_layers": 48}
    published = {
        "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
        "max_position_embeddings": 262144, "max_window_layers": 48,
        "mlp_only_layers": [], "model_type": "KeyeVL2",
        "moe_intermediate_size": 768, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
        "num_key_value_heads": 4, "num_local_experts": 128, "rms_norm_eps": 1e-06,
        "rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default",
                         "type": "default"},
        "rope_theta": 10000000,
        "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                      "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                      "q_chunk_size": 512, "topk": 2048},
        "sliding_window": None, "tie_word_embeddings": False,
        "use_sliding_window": False, "vocab_size": 151936,
    }
    for key, value in published.items():
        assert cfg[key] == value, key
    assert cfg["num_hidden_layers"] == 6
    dep = cfg["deployment"]
    assert dep["pipeline_stages"] * dep["layers_per_stage"] == 48
    serving = cfg["serving"]
    assert serving["max_seq"] == 67584 + 2048 and serving["max_seq"] % 128 == 0


@pytest.fixture
def toy_benchmark(monkeypatch, tmp_path):
    import run as run_module

    cfg = toy_keye.config()
    (tmp_path / "toy-keye.json").write_text(json.dumps(cfg))
    real = loading.benchmark_json()
    bench = dict(
        real,
        configs=[{"name": "toy-keye", "file": str(tmp_path / "toy-keye.json")}],
        workloads=[{"name": "toy-keye-serve", "config": "toy-keye",
                    "traffic": "shared-long-context", "chips": 1}],
    )
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in metric:
            metric["workloads"] = [
                "toy-keye-serve" if w.startswith("keye") else w
                for w in metric["workloads"]
            ]
    cell = toy_keye.workload()
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
        ["--workload", "toy-keye-serve", "--seed", "3000000005",
         "--seconds", "2", "--trace", "0"], require_chip=False,
    )
    line = _last_line(capsys)
    assert rc == 0 and line["correct"] is True, line
    assert line["failed"] == 0 and line["attempted"] > 0
    assert {"tpot_p95_ms", "tokens_per_s", "setup_s"} <= set(line["metrics"])


def test_the_driver_ends_at_once_on_a_program_without_the_tower(monkeypatch):
    """What the parent commit gives: the import fails before a weight is
    drawn."""
    import sys

    monkeypatch.setitem(sys.modules, "znicz_tpu.workflow.sparse_gqa_lm", None)
    drawn = []
    monkeypatch.setattr(keye_weights, "weights", lambda *a: drawn.append(a))
    with pytest.raises(ImportError):
        serve.Server(toy_keye.config(), 1, 10.0)
    assert not drawn
