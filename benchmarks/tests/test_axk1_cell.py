"""The new driver at toy size on the CPU: a sound run is correct, both
controls are not, a token altered where it is produced is not, and the
whole command prints a result line with the cell's metrics."""

import json

import numpy as np
import pytest

import toy_axk1
from harness import loading
from harness.checks import float8

serve = loading.load_module("drivers", "serve_latent_moe")
VOCAB = toy_axk1.config()["vocab_size"]


@pytest.fixture(scope="module")
def window():
    """One warm toy server, one window: what the reference then reads."""
    cfg, mix = toy_axk1.config(), toy_axk1.workload()["traffic"]
    server = serve.Server(cfg, 77, mix["deadline_s"])
    try:
        server.warm(np.random.default_rng(1), 36)
        server.prime_prefix(mix, 77)
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
    # the shared prefix was served from the prefix cache, 4 blocks a hit
    assert measured["delta"].value("znicz_serve_prefix_hits_total") >= 4 * 8


@pytest.mark.parametrize(
    "control", [{"control": float8}, {"cache_control": float8}],
    ids=["float8_products", "float8_cache"],
)
def test_a_control_one_step_of_precision_down_is_not_correct(window, control):
    cfg, mix, w, summary, _ = window
    checks = serve.decide_correct(cfg, w, summary["good"], 77, mix, **control)
    assert not checks.correct, checks.rows
    assert checks.rows[0]["ok"]  # no answer cut short: a limit on the gaps failed


@pytest.fixture
def toy_benchmark(monkeypatch, tmp_path):
    import run as run_module

    cfg = toy_axk1.config()
    (tmp_path / "toy-axk1.json").write_text(json.dumps(cfg))
    real = loading.benchmark_json()
    bench = dict(
        real,
        configs=[{"name": "toy-axk1", "file": str(tmp_path / "toy-axk1.json")}],
        workloads=[{"name": "toy-axk1-serve", "config": "toy-axk1",
                    "traffic": "shared-doc", "chips": 1}],
    )
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in metric:
            metric["workloads"] = [
                "toy-axk1-serve" if w.startswith("axk1") else w
                for w in metric["workloads"]
            ]
    cell = toy_axk1.workload()
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
        ["--workload", "toy-axk1-serve", "--seed", "3000000005", "--seconds",
         "2", "--trace", "0"], require_chip=False,
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
        vocab = VOCAB
        return (pools, (tok + 1) % vocab, pos, done, remaining,
                (out + 1) % vocab, steps, load)

    altered._cache_size = real._cache_size
    monkeypatch.setattr(engine, "_paged_decode_chunk", altered)
    toy_benchmark.main(
        ["--workload", "toy-axk1-serve", "--seed", "6", "--seconds", "2",
         "--trace", "0"], require_chip=False,
    )
    line = _last_line(capsys)
    assert line["correct"] is False, line
