"""The whole of a run with the timed path broken underneath comes out
with ``correct`` false.  ``run.main`` is driven as the command drives it,
except for the look for a chip; the cell and configuration it finds by
name are toy-size ones."""

import json

import pytest

import toy
from harness import loading


@pytest.fixture
def toy_benchmark(monkeypatch, tmp_path):
    """BENCHMARK.json, the configuration files and the cell files as
    ``run.main`` finds them, swapped for toy-size ones."""
    import run as run_module

    cnn, lm = toy.cnn_config(), toy.lm_config()
    files = {"toy-cnn": cnn, "toy-lm": lm}
    for name, cfg in files.items():
        (tmp_path / f"{name}.json").write_text(json.dumps(cfg))
    real = loading.benchmark_json()
    bench = dict(
        real,
        configs=[
            {"name": n, "file": str(tmp_path / f"{n}.json")} for n in files
        ],
        workloads=[
            {"name": "toy-train", "config": "toy-cnn", "traffic": "streamed", "chips": 1},
            {"name": "toy-serve", "config": "toy-lm", "traffic": "steady", "chips": 1},
        ],
    )
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in metric:
            metric["workloads"] = [
                "toy-train" if "alexnet" in w else "toy-serve"
                for w in metric["workloads"]
            ]
    cells = {
        "toy-train.json": toy.train_workload(),
        "toy-serve.json": toy.serve_workload(),
    }
    monkeypatch.setattr(loading, "benchmark_json", lambda: bench)
    monkeypatch.setattr(
        loading, "load_json",
        lambda *rel: cells[rel[-1]] if rel[0] == "workloads" else None,
    )
    monkeypatch.setattr(
        run_module.Run, "cache_dir", property(
            lambda self: str(tmp_path), lambda self, v: None
        ), raising=False,
    )
    toy.point_model_file_at(cnn)
    return run_module


def _last_line(capsys) -> dict:
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_sound_toy_runs_are_correct(toy_benchmark, capsys):
    for cell in ("toy-train", "toy-serve"):
        rc = toy_benchmark.main(
            ["--workload", cell, "--seed", "3000000005", "--seconds", "2",
             "--trace", "0"], require_chip=False,
        )
        line = _last_line(capsys)
        assert rc == 0 and line["correct"] is True, line
        assert line["failed"] == 0 and line["attempted"] > 0
        assert "setup_s" in line["metrics"]


def test_a_step_that_returns_its_state_unchanged_is_not_correct(
    toy_benchmark, capsys, monkeypatch
):
    import jax
    import jax.numpy as jnp

    from znicz_tpu.workflow.workflow import Workflow

    build = Workflow._build_steps

    def build_broken(self):
        build(self)
        step = self._train_step

        def frozen(state, x, y, mask, lr_scale, acc, ctx):
            kept = jax.tree_util.tree_map(jnp.copy, state)  # it is donated
            _, acc, watch = step(state, x, y, mask, lr_scale, acc, ctx)
            return kept, acc, watch

        self._train_step = frozen

    monkeypatch.setattr(Workflow, "_build_steps", build_broken)
    toy_benchmark.main(
        ["--workload", "toy-train", "--seed", "6", "--seconds", "1",
         "--trace", "0"], require_chip=False,
    )
    line = _last_line(capsys)
    assert line["correct"] is False, line


def test_a_token_altered_where_it_is_produced_is_not_correct(
    toy_benchmark, capsys, monkeypatch
):
    from znicz_tpu.services import engine

    real = engine._paged_decode_chunk

    def altered(*args, **kwargs):
        pools, tok, pos, done, remaining, out, steps = real(*args, **kwargs)
        vocab = toy.lm_config()["vocab_size"]
        return (pools, (tok + 1) % vocab, pos, done, remaining,
                (out + 1) % vocab, steps)

    altered._cache_size = real._cache_size
    monkeypatch.setattr(engine, "_paged_decode_chunk", altered)
    toy_benchmark.main(
        ["--workload", "toy-serve", "--seed", "6", "--seconds", "2",
         "--trace", "0"], require_chip=False,
    )
    line = _last_line(capsys)
    assert line["correct"] is False, line
