"""Test harness: simulate an 8-device mesh on CPU.

Mirrors SURVEY.md section 4's rebuild strategy: all sharding/collective logic
is unit-testable without TPUs via xla_force_host_platform_device_count.
Must run before jax is imported anywhere.

``ZNICZ_TEST_TPU=1`` leaves the platform alone so a run on the chip
(``chiprun -- env ZNICZ_TEST_TPU=1 python -m pytest tests/test_pallas.py``)
reaches the TPU-gated kernel timing assertions; the virtual-mesh
parallelism tests need the default 8-device CPU setup.
"""

import os

if os.environ.get("ZNICZ_TEST_TPU") != "1":
    os.environ["JAX_PLATFORMS"] = "cpu"
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count=8"
        ).strip()

# The tests' compiles — and those of every child process they spawn — stay
# out of the persistent cache the program's entry points switch on
# (core/backend.py): a tier-1 run must not depend on, or leave behind, a
# cache directory.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import jax  # noqa: E402
import pytest  # noqa: E402

# Golden tests compare XLA ops against naive numpy: use full fp32 matmuls.
# Production code keeps JAX's fast default (bf16-on-MXU) — see bench.py.
jax.config.update("jax_default_matmul_precision", "highest")


@pytest.fixture(autouse=True)
def _fresh_prng():
    """Reseed the named-generator registry per test for reproducibility."""
    from znicz_tpu.core import prng

    prng.reset()
    prng.seed_all(1234)
    yield
    prng.reset()


@pytest.fixture(autouse=True)
def _fresh_config():
    from znicz_tpu.core.config import root

    saved = root.to_dict()
    yield
    root.clear()
    root.update(saved)
