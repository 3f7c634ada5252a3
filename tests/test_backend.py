"""The device policy and the compile cache (znicz_tpu/core/backend.py), the
launcher that applies them, and chip_smoke.py's refusal to pass off-chip.

This process runs on the CPU (conftest), so whatever needs a fresh backend
choice — "the TPU was asked for and is not there", "two processes agree on
the cache path" — runs in a child process.
"""

import json
import os
import subprocess
import sys

import jax
import pytest

from znicz_tpu.core import backend

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True, scope="module")
def _cpu_backend_is_up():
    """Freeze this process's platform BEFORE a test asks for another: a
    ``select("tpu")`` on a process with no backend yet would repoint every
    later test in the session at a chip that is not there."""
    assert jax.devices()[0].platform == "cpu"


def _child_env(**overrides):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    for key, value in overrides.items():
        if value is None:
            env.pop(key, None)
        else:
            env[key] = value
    return env


class TestDevicePolicy:
    def test_device_tpu_on_a_cpu_process_is_the_typed_error(self):
        # the backend is already up on the CPU here: asking for the TPU
        # must raise, not hand back the CPU devices
        with pytest.raises(backend.NoAcceleratorError, match="already runs"):
            backend.require("tpu")
        assert backend.require("cpu")[0].platform == "cpu"
        assert backend.require(None)[0].platform == "cpu"  # JAX_PLATFORMS

    def test_pallas_interprets_exactly_when_off_the_tpu(self, monkeypatch):
        assert not backend.on_tpu() and backend.pallas_interpret()
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert backend.on_tpu() and not backend.pallas_interpret()

    def test_cli_device_tpu_without_a_tpu_is_an_error_not_a_cpu_run(self):
        """``python -m znicz_tpu znicz_tpu/models/wine.py --device tpu``
        on a machine with no chip: non-zero exit, the typed error, and
        not one epoch trained."""
        proc = subprocess.run(
            [
                sys.executable, "-m", "znicz_tpu",
                os.path.join(REPO, "znicz_tpu", "models", "wine.py"),
                "--device", "tpu", "--stop-after", "1",
            ],
            env=_child_env(JAX_PLATFORMS=None),
            capture_output=True, text=True, timeout=300, cwd=REPO,
        )
        if proc.returncode == 0:
            pytest.skip("this machine has a TPU: the run was legitimate")
        assert "NoAcceleratorError" in proc.stderr
        assert "epoch 0" not in proc.stderr + proc.stdout

    def test_optimize_workers_on_the_accelerator_errors_before_spawning(
        self, monkeypatch
    ):
        from znicz_tpu.core import subproc
        from znicz_tpu.launcher import run_args

        def no_pool(*a, **kw):
            raise AssertionError("a worker pool was started")

        monkeypatch.setattr(subproc, "run_pool", no_pool)
        with pytest.raises(backend.AcceleratorWorkersError, match="2 worker"):
            run_args(
                [
                    os.path.join(REPO, "znicz_tpu", "models", "wine.py"),
                    "--device", "tpu",
                    "--optimize", "1", "--optimize-workers", "2",
                ]
            )

    def test_supervisor_parent_never_touches_the_backend(self, tmp_path):
        """The supervising parent only loops a child process: it must
        return without initialising a backend, or — on a chip — the child
        it starts could never open the device."""
        code = (
            "import sys\n"
            "from jax._src.xla_bridge import backends_are_initialized\n"
            "from znicz_tpu import launcher\n"
            "launcher.supervise = lambda args, argv: 0\n"
            "try:\n"
            "    launcher.run_args([sys.argv[1], '--supervise'])\n"
            "except SystemExit as exc:\n"
            "    assert exc.code == 0, exc.code\n"
            "print('INITIALISED' if backends_are_initialized() else 'CLEAN')\n"
        )
        proc = subprocess.run(
            [
                sys.executable, "-c", code,
                os.path.join(REPO, "znicz_tpu", "models", "wine.py"),
            ],
            env=_child_env(), capture_output=True, text=True, timeout=300,
            cwd=str(tmp_path),
        )
        assert proc.returncode == 0, proc.stderr[-2000:]
        assert proc.stdout.split()[-1] == "CLEAN"


class TestCompileCache:
    def test_env_directory_wins_and_code_sets_nothing(self, monkeypatch):
        before = jax.config.jax_compilation_cache_dir
        monkeypatch.setenv(backend.COMPILE_CACHE_ENV, "/somewhere/else")
        assert backend.enable_compile_cache() == "/somewhere/else"
        assert jax.config.jax_compilation_cache_dir == before

    def test_unset_means_one_fixed_path_in_every_process(self, tmp_path):
        """Two processes, started from different directories, with the
        variable unset: the same in-checkout path (the path is part of
        the cache key, so a moving directory would never hit).  A third,
        with the variable set, takes jax's own reading of it."""
        code = (
            "import json, jax\n"
            "from znicz_tpu.core import backend\n"
            "used = backend.enable_compile_cache()\n"
            "print(json.dumps([used, jax.config.jax_compilation_cache_dir]))\n"
        )

        def start(cwd, cache_dir):
            return subprocess.Popen(
                [sys.executable, "-c", code],
                env=_child_env(JAX_COMPILATION_CACHE_DIR=cache_dir),
                stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
                cwd=str(cwd),
            )

        given = str(tmp_path / "given")
        procs = [
            start(tmp_path, None), start(REPO, None), start(tmp_path, given)
        ]
        outs = []
        for proc in procs:
            out, err = proc.communicate(timeout=300)
            assert proc.returncode == 0, err[-2000:]
            outs.append(json.loads(out.splitlines()[-1]))
        fixed = os.path.join(REPO, ".jax_cache")
        assert outs[0] == outs[1] == [fixed, fixed]
        assert outs[2] == [given, given]


class TestChipSmokeOffChip:
    def test_fails_at_the_device_phase_and_prints_no_result(self, capsys):
        sys.path.insert(0, REPO)
        try:
            import chip_smoke
        finally:
            sys.path.remove(REPO)
        with pytest.raises(backend.NoAcceleratorError):
            chip_smoke.main([], sizes=chip_smoke.TOY)
        out = capsys.readouterr().out
        assert '"ok"' not in out and '"passed"' not in out
