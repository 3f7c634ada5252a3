"""Genetic optimizer tests (veles --optimize parity)."""

import numpy as np
import pytest

from znicz_tpu.core import prng
from znicz_tpu.core.config import root
from znicz_tpu.genetics import GeneticOptimizer, Tune, find_tunables


class TestTunables:
    def test_find_in_tree_and_layer_dicts(self):
        root.g.update({"a": Tune(0.1, 0.0, 1.0), "nested": {"b": Tune(2, 1, 5, "int")}})
        root.g.layers = [
            {"type": "all2all", "<-": {"learning_rate": Tune(0.01, 1e-4, 1.0)}}
        ]
        found = find_tunables(root.g)
        assert len(found) == 3
        keys = {k for _, k, _ in found}
        assert keys == {"a", "b", "learning_rate"}

    def test_clip_kinds(self):
        t = Tune(2, 1, 5, "int")
        assert t.clip(7.6) == 5 and t.clip(0.2) == 1 and t.clip(3.4) == 3
        f = Tune(0.1, 0.0, 1.0)
        assert f.clip(2.0) == 1.0


class TestGeneticOptimizer:
    def test_minimizes_quadratic(self):
        prng.seed_all(123)
        root.q.update({"x": Tune(5.0, -10.0, 10.0), "y": Tune(-5.0, -10.0, 10.0)})
        tunables = find_tunables(root.q)

        def evaluate(genome):
            x, y = genome
            return (x - 3.0) ** 2 + (y + 1.0) ** 2

        opt = GeneticOptimizer(
            evaluate, tunables, population_size=12, mutation_rate=0.4
        )
        result = opt.run(generations=15)
        assert result["best_fitness"] < 0.5
        x, y = result["best_genome"]
        assert abs(x - 3.0) < 1.0 and abs(y + 1.0) < 1.0
        # apply_genome writes back into the config tree
        opt.apply_genome(result["best_genome"])
        assert root.q.x == x and root.q.y == y

    def test_no_tunables_raises(self):
        with pytest.raises(ValueError, match="no Tune leaves"):
            GeneticOptimizer(lambda g: 0.0, [])

    def test_deterministic_under_seed(self):
        def run_once():
            prng.reset()
            prng.seed_all(7)
            tunables = [({}, "x", Tune(0.0, -5.0, 5.0))]
            opt = GeneticOptimizer(
                lambda g: g[0] ** 2, tunables, population_size=6
            )
            return opt.run(generations=5)["best_fitness"]

        assert run_once() == run_once()


class TestConcurrentOptimize:
    def test_batch_evaluation_is_generationwise_and_concurrent(self):
        # the GA hands the WHOLE uncached generation to evaluate_batch at
        # once — concurrency happens there (wall-clock scaling check)
        import time
        from concurrent.futures import ThreadPoolExecutor

        calls = []

        def eval_batch(genomes):
            calls.append(len(genomes))
            with ThreadPoolExecutor(4) as ex:
                return list(
                    ex.map(
                        lambda g: (time.sleep(0.2), g[0] ** 2)[1], genomes
                    )
                )

        prng.seed_all(5)
        tunables = [({}, "x", Tune(0.0, -5.0, 5.0))]
        opt = GeneticOptimizer(
            None, tunables, population_size=8, evaluate_batch=eval_batch
        )
        t0 = time.time()
        result = opt.run(generations=1)
        dt = time.time() - t0
        assert max(calls) >= 4  # generation-sized batches, not per-genome
        assert dt < 8 * 0.2 * 0.8, dt  # faster than sequential => concurrent
        assert np.isfinite(result["best_fitness"])

    @pytest.mark.slow
    def test_worker_processes_deterministic_and_worker_count_invariant(
        self, tmp_path
    ):
        # VERDICT r1 #5 gate: N-way concurrent --optimize, deterministic
        # given seeds — and identical for every worker count
        from znicz_tpu.genetics import optimize_workflow
        from znicz_tpu.launcher import Launcher, _load_module, make_parser

        wf_py = tmp_path / "wf.py"
        wf_py.write_text(
            "from znicz_tpu.core.config import root\n"
            "from znicz_tpu.genetics import Tune\n"
            "import znicz_tpu.models.wine as wine\n"
            "root.wine.update({'lr': Tune(0.3, 0.05, 0.5)})\n"
            "def run(load, main):\n"
            "    lr = root.wine.get('lr')\n"
            "    layers = [dict(l) for l in wine.DEFAULTS['layers']]\n"
            "    for l in layers:\n"
            "        l['<-'] = {**l['<-'], 'learning_rate': lr}\n"
            "    root.wine.layers = layers\n"
            "    load(wine.build_workflow)\n"
            "    main()\n"
        )
        args = make_parser().parse_args(
            [str(wf_py), "--random-seed", "11", "--stop-after", "2"]
        )

        def run_once(n_workers):
            prng.reset()
            prng.seed_all(11)
            from znicz_tpu.core.config import root as r
            from znicz_tpu.genetics import find_tunables

            # reload each run: the previous search's apply_genome left the
            # best VALUE where the Tune leaf was (that is its contract)
            module = _load_module(str(wf_py), "wf_concurrent_test_mod")
            return optimize_workflow(
                module,
                Launcher(args),
                generations=1,
                tunables=find_tunables(r),
                n_workers=n_workers,
                population_size=3,
            )

        r2 = run_once(2)
        r1 = run_once(1)
        assert np.isfinite(r2["best_fitness"])
        assert r2["best_fitness"] == r1["best_fitness"]
        assert r2["best_genome"] == r1["best_genome"]


class TestAcceleratorWorkerPolicy:
    """One process per chip (core/backend.py): a pool that cannot get
    the accelerator is refused BEFORE any child starts."""

    def test_two_accelerator_workers_are_refused(self):
        from znicz_tpu.core import backend

        with pytest.raises(backend.AcceleratorWorkersError, match="2 worker"):
            backend.check_workers(2, "tpu")
        # the CPU is the documented recipe for concurrent evaluations,
        # and what the suite's own JAX_PLATFORMS=cpu pools run on
        backend.check_workers(4, "cpu")
        backend.check_workers(4, None)
        backend.check_workers(0, "tpu")  # in-process search: no pool

    def test_parent_holding_the_chip_refuses_even_one_worker(
        self, monkeypatch
    ):
        from znicz_tpu.core import backend

        backend.check_workers(1, "tpu")  # parent off the chip: fine
        monkeypatch.setattr(backend, "holds_accelerator", lambda: True)
        with pytest.raises(
            backend.AcceleratorWorkersError, match="already holds"
        ):
            backend.check_workers(1, "tpu")


class TestOptimizeCLI:
    def test_optimize_flag_end_to_end(self, tmp_path):
        from znicz_tpu.launcher import run_args

        wf_py = tmp_path / "wf.py"
        wf_py.write_text(
            "from znicz_tpu.core.config import root\n"
            "from znicz_tpu.genetics import Tune\n"
            "from znicz_tpu.models.wine import build_workflow\n"
            "root.wine.layers = None  # use DEFAULTS, then tune lr below\n"
            "import znicz_tpu.models.wine as wine\n"
            "root.wine.update({'lr': Tune(0.3, 0.05, 0.5)})\n"
            "def run(load, main):\n"
            "    lr = root.wine.get('lr')\n"
            "    layers = [dict(l) for l in wine.DEFAULTS['layers']]\n"
            "    for l in layers:\n"
            "        l['<-'] = {**l['<-'], 'learning_rate': lr}\n"
            "    root.wine.layers = layers\n"
            "    load(wine.build_workflow)\n"
            "    main()\n"
        )
        out = tmp_path / "best.znicz"
        launcher = run_args(
            [
                str(wf_py),
                "--random-seed", "11",
                "--stop-after", "2",
                "--optimize", "2",
                "--export", str(out),
            ]
        )
        assert launcher.result is not None
        assert np.isfinite(launcher.result["best_fitness"])
        assert len(launcher.result["history"]) == 2
        # export happens once, AFTER the search, with the best config applied
        assert out.read_bytes()[:8] == b"ZNICZT01"
