"""The benchmark's own tests run on the CPU, apart from the repository's
tier-1 suite: ``JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests -q``."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax  # noqa: E402

# the program's own arithmetic at full float32, so that toy-size
# comparisons with the plain reference read rounding and nothing else
jax.config.update("jax_default_matmul_precision", "highest")
jax.config.update("jax_enable_compilation_cache", False)
