"""Cross-backend golden tests: CPU vs the real TPU chip.

The literal rebuild of the reference's numpy-vs-OpenCL-vs-CUDA golden checks
(SURVEY.md §4): the same seeded computation must agree across backends.  The
suite itself runs on the virtual CPU mesh (conftest) and never opens the
chip, so each half runs in a SUBPROCESS whose ``JAX_PLATFORMS`` names its one
platform; skipped when no TPU answers — or when this process holds it
(``ZNICZ_TEST_TPU=1``): a chip belongs to one process at a time.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PROBE = """
import jax
print("OK" if jax.devices()[0].platform == "tpu" else "NO")
"""

_COMPUTE = """
import json
import jax, jax.numpy as jnp
import numpy as np
from znicz_tpu.core import prng
from znicz_tpu.loader import datasets
from znicz_tpu.workflow import StandardWorkflow

prng.seed_all(777)
loader = datasets.mnist(n_train=128, n_test=0, minibatch_size=64)
wf = StandardWorkflow(
    loader,
    [{"type": "all2all_tanh", "->": {"output_sample_shape": 16}},
     {"type": "softmax", "->": {"output_sample_shape": 10}}],
    decision_config={"max_epochs": 2},
    default_hyper={"learning_rate": 0.1, "gradient_moment": 0.9},
)
wf.initialize(seed=777)
dec = wf.run()
out = {
    "losses": [e["train"]["loss"] for e in dec.history],
    "n_err": [e["train"]["n_err"] for e in dec.history],
    "w_sum": float(jnp.sum(wf.state.params[0]["weights"])),
}
print("RESULT:" + json.dumps(out))
"""


def _run_subprocess(code: str, *, platform: str) -> str:
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + ":" + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = platform
    env.pop("XLA_FLAGS", None)  # one device per half
    r = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True,
        text=True,
        env=env,
        timeout=540,
        cwd=REPO,
    )
    if r.returncode != 0:
        raise RuntimeError(r.stderr[-1500:])
    return r.stdout


@pytest.fixture(scope="session")
def tpu_reachable():
    from znicz_tpu.core import backend

    if backend.holds_accelerator():
        pytest.skip("this process holds the chip; a child cannot open it")
    try:
        out = _run_subprocess(_PROBE, platform="tpu")
    except (RuntimeError, subprocess.TimeoutExpired):
        pytest.skip("no TPU reachable")
    if "OK" not in out:
        pytest.skip("no TPU reachable")
    return True


def _extract(stdout: str) -> dict:
    for line in stdout.splitlines():
        if line.startswith("RESULT:"):
            return json.loads(line[len("RESULT:"):])
    raise AssertionError(f"no RESULT line in: {stdout[-500:]}")


class TestCrossBackendGolden:
    def test_seeded_training_matches_cpu(self, tpu_reachable):
        """Two epochs of seeded MNIST training must agree across backends:
        identical error counts, near-identical losses and weight sums
        (tolerance band per SURVEY.md §7 — fusion differences are real)."""
        cpu = _extract(_run_subprocess(_COMPUTE, platform="cpu"))
        tpu = _extract(_run_subprocess(_COMPUTE, platform="tpu"))
        assert cpu["n_err"] == tpu["n_err"]
        np.testing.assert_allclose(
            cpu["losses"], tpu["losses"], rtol=2e-2
        )
        np.testing.assert_allclose(
            cpu["w_sum"], tpu["w_sum"], rtol=2e-2
        )
