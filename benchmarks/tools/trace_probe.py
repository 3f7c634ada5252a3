"""A trace of a few small matrix products, to see what this installation's
profiler writes before a real cell is traced: file size, then every plane
and line with its event count (``trace_outline.py --count``)."""

import glob
import os
import subprocess
import sys
import tempfile
import time

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from harness.profile import Capture  # noqa: E402


def main():
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((2048, 2048), jnp.bfloat16)
    f(x).block_until_ready()
    keep = tempfile.mkdtemp(prefix="trace_probe_")
    capture = Capture(keep_dir=keep)
    capture.start()
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("probe/loop"):
        while time.perf_counter() - t0 < 0.3:
            f(x).block_until_ready()
            time.sleep(0.002)
    capture.stop()
    print(capture.reduced)
    path = glob.glob(os.path.join(keep, "*.xplane.pb"))[0]
    subprocess.run(
        [sys.executable, os.path.join(os.path.dirname(__file__), "trace_outline.py"),
         path, "--count"], check=False,
    )


if __name__ == "__main__":
    main()
