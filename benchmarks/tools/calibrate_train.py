"""Read, on the chip, what a ``train_epochs`` cell's limits are set from:
for each seed the program's first steps against the plain reference (the
sound runs) and the reference one step of precision lower against itself
(the control).  No measured window.  One process, every seed.

    python3 benchmarks/tools/calibrate_train.py <cell> <seed> [<seed> ...]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import run as run_module  # noqa: E402
from harness import loading  # noqa: E402
from harness.checks import Checks, float8  # noqa: E402


def main(argv) -> int:
    import jax
    import jax.numpy as jnp

    cell_name, seeds = argv[0], [int(s) for s in argv[1:]]
    cell, config, workload = run_module.load_cell(cell_name)
    devices = run_module.open_devices(int(cell["chips"]))
    from znicz_tpu.core import backend

    backend.enable_compile_cache()
    train = loading.load_module("drivers", "train_epochs")

    loose = {k: float("inf") for k in workload["traffic"]["limits"]}
    for seed in seeds:
        t0 = time.perf_counter()
        args = argparse.Namespace(seed=seed, seconds=0.0, trace=0)
        run = run_module.Run(cell, workload, config, args, devices[: int(cell["chips"])])
        live = train.setup(run)
        live["wf"].state = None
        live.pop("wf")
        params0 = jax.tree_util.tree_map(jnp.asarray, live["params0"])
        key = jax.random.wrap_key_data(jnp.asarray(live["key_data"]))
        want = train.follow(config, run.traffic, params0, key, live["records"])
        got = train.program_readings(config, live["params0"], live["records"])
        low = train.follow(
            config, run.traffic, params0, key, live["records"], cast=float8
        )
        for label, readings in (("program", got), ("control", low)):
            checks = Checks()
            train.compare(checks, readings, want, loose)
            print(json.dumps({
                "cell": cell_name, "seed": seed, "side": label,
                **{r["name"]: r["value"] for r in checks.rows},
                "losses": readings["losses"],
                "seconds": round(time.perf_counter() - t0, 1),
            }), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
