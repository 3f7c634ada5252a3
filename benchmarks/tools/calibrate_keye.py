"""Read, on the chip, what the limits of the ``serve_sparse_gqa`` cell are
set from, or sweep its arrival rate for the knee.  One process.

    python3 benchmarks/tools/calibrate_keye.py <cell> gaps <seconds> <seed> [<seed> ...]
    python3 benchmarks/tools/calibrate_keye.py <cell> sweep <seconds> <seed> <rate> [<rate> ...] [gaps]

``gaps``: per seed a new server (weights from the seed, the shared prefix
primed), a window of <seconds> at the cell's own load, then the
served-token logit gaps of the sampled requests for the program and for
the five controls of ``drivers/serve_sparse_gqa.CONTROLS``, the prefix's
honest state computed once for all six.  ``sweep``: one primed server, one
ramp and window a rate, ``tools/calibrate_dots3.py``'s line for each (the
knee is read as there); with a trailing ``gaps`` the last window's
requests are then read like a ``gaps`` seed's, on the same weights.
"""

from __future__ import annotations

import copy
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import run as run_module  # noqa: E402
from calibrate_dots3 import _sweep_line  # noqa: E402
from harness import loading  # noqa: E402


def _gap_lines(serve, cell_name, cfg, w, summary, seed, mix):
    sample = serve.sample_for_check(summary["good"], seed, int(mix["check_requests"]))
    reading = serve.Reading(cfg, w, sample, mix)
    for side, control in [("program", None)] + sorted(serve.CONTROLS.items()):
        t0 = time.perf_counter()
        gaps, shared = reading.gaps(control)
        print(json.dumps({
            "cell": cell_name, "seed": seed, "side": side,
            "tokens": int(gaps.size), "widest": float(gaps.max()),
            "mean": float(gaps.mean()),
            "nonzero_share": float((gaps > 0).mean()),
            "p99": float(np.percentile(gaps, 99)),
            "selected_keys_not_shared_mean": float(1.0 - shared.mean()),
            "selected_keys_not_shared_most": float(1.0 - shared.min()),
            "prefix_state_s": reading.state_s,
            "read_s": time.perf_counter() - t0,
            **summary["metrics"], "failed": summary["failed"],
        }), flush=True)


def main(argv) -> int:
    cell_name, mode, seconds = argv[0], argv[1], float(argv[2])
    cell, cfg, workload = run_module.load_cell(cell_name)
    mix = workload["traffic"]
    devices = run_module.open_devices(int(cell["chips"]))
    from znicz_tpu.core import backend

    backend.enable_compile_cache()
    serve = loading.load_module("drivers", workload["driver"])
    deadline_s = float(mix["deadline_s"])

    def peak():
        stats = devices[0].memory_stats() or {}
        return stats.get("peak_bytes_in_use", 0) + stats.get("peak_bytes_reserved", 0)

    if mode == "gaps":
        for seed in (int(s) for s in argv[3:]):
            server = serve.Server(cfg, seed, deadline_s)
            try:
                server.prime(mix, seed)
                measured = serve.measure(server, mix, seed, seconds)
            finally:
                server.close()
            server.release()
            summary = serve.summarise(measured, seconds, deadline_s)
            _gap_lines(serve, cell_name, cfg, server.weights, summary, seed, mix)
            del server
        return 0

    then_gaps = argv[-1] == "gaps"
    seed = int(argv[3])
    rates = [float(r) for r in (argv[4:-1] if then_gaps else argv[4:])]
    server = serve.Server(cfg, seed, deadline_s)
    try:
        t0 = time.perf_counter()
        server.prime(mix, seed)
        print(json.dumps({"primed_s": time.perf_counter() - t0}), flush=True)
        for rate in rates:
            swept = copy.deepcopy(mix)
            swept["arrivals"]["rate_per_s"] = rate
            measured = serve.measure(server, swept, seed, seconds)
            summary = serve.summarise(measured, seconds, deadline_s)
            print(json.dumps(
                dict(_sweep_line(cfg, rate, seconds, measured, summary),
                     memory_peak_bytes=peak())
            ), flush=True)
    finally:
        server.close()
    if then_gaps:
        server.release()
        _gap_lines(serve, cell_name, cfg, server.weights, summary, seed, swept)
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
