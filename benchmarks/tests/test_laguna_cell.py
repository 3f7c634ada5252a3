"""The ``serve_gated_window_moe`` driver at toy size on the CPU: a sound run
is correct, maps the primed prefix from the prefix cache in BOTH kinds of
cache blocks (the window kind's last window alone) and cuts no chain; each
of the five controls is not correct (it fails a limit on the gaps); the
readers read what the window counted; and the weights count what the
configuration states."""

import numpy as np
import pytest

import toy_laguna
from harness import laguna_weights, laguna_work, loading, registry

serve = loading.load_module("drivers", "serve_gated_window_moe")
SEED = 77


@pytest.fixture(scope="module")
def window():
    """One warm, primed toy server, one window: what the reference reads."""
    cfg, mix = toy_laguna.config(), toy_laguna.workload()["traffic"]
    server = serve.Server(cfg, SEED, mix["deadline_s"])
    try:
        before = registry.read()
        server.prime(mix, SEED)
        primed = registry.Delta(before, registry.read())
        measured = serve.measure(server, mix, SEED, 2.0)
    finally:
        server.close()
    summary = serve.summarise(measured, 2.0, mix["deadline_s"])
    return cfg, mix, server.weights, summary, measured, primed


def test_the_unbroken_path_is_correct_and_shares_the_prefix_in_both_kinds(window):
    cfg, mix, w, summary, measured, primed = window
    assert summary["failed"] == 0 and len(summary["good"]) >= 8
    checks = serve.decide_correct(cfg, w, summary["good"], SEED, mix)
    print(checks.rows)
    assert checks.correct, checks.rows
    assert [r["name"] for r in checks.rows] == [
        "answers_cut_short", "served_logit_gap_widest", "served_logit_gap_mean",
    ]
    delta = measured["delta"]
    prompts = delta.value("znicz_serve_prompt_tokens_total")
    cached = delta.value("znicz_serve_prefix_cached_tokens_total")
    hits = delta.value("znicz_serve_prefix_hit_requests_total")
    # every request admitted inside the window maps the prefix's 8 blocks
    # whole (one due at the window's end may be admitted after it closes)
    assert cached == 64 * hits and hits >= len(measured["outcomes"]) - 2
    assert 0.4 < cached / prompts < 1
    mapped = {
        kind: delta.value("znicz_serve_prefix_blocks_mapped_total", kind=kind)
        for kind in ("global", "window")
    }
    # a window of 24 keys at a block of 8: the last 3 blocks of the match
    assert mapped == {"global": 8 * hits, "window": 3 * hits}
    assert not delta.value("znicz_serve_prefix_chain_cut_total")
    # the priming calls: the prefix alone maps nothing, the two after it do
    assert primed.value("znicz_serve_prefix_hit_requests_total") == 2
    reader = loading.load_module("layer_metrics", "cache.prefix_window_blocks_per_hit")
    assert reader.read({"registry": delta, "config": cfg}) == 3
    hit = loading.load_module("layer_metrics", "moe.small_experts_hit_per_layer")
    assert 0 < hit.read({"registry": delta, "config": cfg}) <= 16
    assert hit.read({"registry": delta, "config": {"model_type": "other"}}) is None


@pytest.mark.parametrize("control", sorted(serve.CONTROLS))
def test_a_control_is_not_correct(window, control):
    """One step of precision down in every product or in the cached K and
    V, the gate left out, the window read twice as wide (the control's
    ``window`` is the cell's 1,024: here 48), plain rotary on the full
    layers."""
    cfg, mix, w, summary, _, _ = window
    arguments = dict(serve.CONTROLS[control])
    if "window" in arguments:
        arguments["window"] = 2 * cfg["sliding_window"]
    checks = serve.decide_correct(
        cfg, w, summary["good"], SEED, mix, control=arguments
    )
    print(control, checks.rows)
    assert not checks.correct, checks.rows
    assert checks.rows[0]["ok"]  # no answer cut short: a limit on the gaps failed
    assert not (checks.rows[1]["ok"] and checks.rows[2]["ok"])


def test_the_weights_count_what_the_configuration_states():
    cfg = loading.load_json("configs", "laguna-xs2-stage1.json")
    held = cfg["parameters_held"]
    assert laguna_weights.n_parameters(cfg) == held["total"] == 5563547648
    assert held["bytes"] == 2 * held["total"]
    assert held["total"] == (
        held["embedding_and_head"] + held["layer_0"]
        + 5 * held["a_sparse_sliding_layer"] + held["a_sparse_full_layer"]
    )
    assert laguna_work.layers_of(cfg) == {"global": 2, "window": 5}
    assert laguna_work.routed_layers(cfg) == 6
    assert (laguna_work.heads_of(cfg, "global"), laguna_work.heads_of(cfg, "window")) == (48, 64)
    assert laguna_work.cache_row_bytes(cfg) == 4096
    # every weight but the experts and the embedding
    assert laguna_work.always_read_params(cfg) == (
        held["total"] - held["embedding_and_head"] // 2 - 6 * held["experts_a_layer"]
    )
    toy = toy_laguna.config()
    w = laguna_weights.weights(toy, 5)
    assert float(np.abs(np.asarray(w["head"], np.float32)[:, 0]).max()) == 0.0
    assert laguna_weights.n_parameters(toy) == sum(
        int(np.prod(leaf.shape))
        for leaf in [w["embed"], w["head"]]
        + [v for b in w["blocks"] for k, v in b.items() if v.ndim > 1]
    )


def test_the_configuration_keeps_every_published_key():
    """Every key of the catalog's row is in the file at its value, but for
    the one listed as reduced; the per-layer lists are kept whole."""
    cfg = loading.load_json("configs", "laguna-xs2-stage1.json")
    assert cfg["reduced"] == ["num_hidden_layers"]
    assert cfg["published"] == {"num_hidden_layers": 40}
    published = {
        "model_type": "laguna", "vocab_size": 100352, "hidden_size": 2048,
        "intermediate_size": 8192, "num_attention_heads": 48,
        "num_key_value_heads": 8, "head_dim": 128,
        "max_position_embeddings": 262144, "attention_bias": False,
        "rms_norm_eps": 1e-06, "num_experts": 256, "num_experts_per_tok": 8,
        "moe_intermediate_size": 512, "shared_expert_intermediate_size": 512,
        "tie_word_embeddings": False, "gating": True, "sliding_window": 512,
        "moe_apply_router_weight_on_input": False, "partial_rotary_factor": 0.5,
        "moe_routed_scaling_factor": 2.5,
    }
    for key, value in published.items():
        assert cfg[key] == value, key
    full = cfg["rope_parameters"]["full_attention"]
    assert (full["rope_type"], full["factor"], full["beta_fast"]) == ("yarn", 64, 64)
    assert full["attention_factor"] == 1.4158883083359672
    assert cfg["rope_parameters"]["sliding_attention"]["rope_theta"] == 10000
    assert cfg["rope_parameters"]["original_max_position_embeddings"] == 4096
    for key in ("layer_types", "mlp_layer_types", "num_attention_heads_per_layer"):
        assert len(cfg[key]) == 40
    assert cfg["num_attention_heads_per_layer"][:7] == [48, 64, 64, 64, 48, 64, 64]
    assert cfg["num_hidden_layers"] == 7 and cfg["deployment"]["chips"] == 6
    serving = cfg["serving"]
    assert serving["max_seq"] == 20480 + 2048 and serving["max_seq"] % 128 == 0
    assert serving["prefix_cache"] is True


@pytest.fixture
def toy_benchmark(monkeypatch, tmp_path):
    import json

    import run as run_module

    cfg = toy_laguna.config()
    (tmp_path / "toy-laguna.json").write_text(json.dumps(cfg))
    real = loading.benchmark_json()
    bench = dict(
        real,
        configs=[{"name": "toy-laguna", "file": str(tmp_path / "toy-laguna.json")}],
        workloads=[{"name": "toy-laguna-serve", "config": "toy-laguna",
                    "traffic": "agent-turns", "chips": 1}],
    )
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in metric:
            metric["workloads"] = [
                "toy-laguna-serve" if w.startswith("laguna") else w
                for w in metric["workloads"]
            ]
    cell = toy_laguna.workload()
    monkeypatch.setattr(loading, "benchmark_json", lambda: bench)
    monkeypatch.setattr(
        loading, "load_json",
        lambda *rel: cell if rel[0] == "workloads" else None,
    )
    return run_module


def _last_line(capsys) -> dict:
    import json

    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_the_command_prints_the_cell_s_result_line(toy_benchmark, capsys):
    rc = toy_benchmark.main(
        ["--workload", "toy-laguna-serve", "--seed", "3000000005",
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

    monkeypatch.setitem(sys.modules, "znicz_tpu.workflow.gated_window_lm", None)
    drawn = []
    monkeypatch.setattr(laguna_weights, "weights", lambda *a: drawn.append(a))
    with pytest.raises(ImportError):
        serve.Server(toy_laguna.config(), 1, 10.0)
    assert not drawn
