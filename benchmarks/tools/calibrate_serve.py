"""Read, on the chip, what a ``serve_open_loop`` cell's limits are set
from, or sweep its arrival rate for the knee.  One process; a new server
(weights from the seed) for every seed.

    python3 benchmarks/tools/calibrate_serve.py <cell> gaps <seconds> <seed> [<seed> ...]
    python3 benchmarks/tools/calibrate_serve.py <cell> sweep <seconds> <seed> <rate> [<rate> ...]

``gaps``: per seed, a window of <seconds> at the cell's own load, then
the served-token logit gaps of the sampled requests for the program and
for the float8 control.  ``sweep``: one server, one window per rate;
the knee is the highest rate whose window ends with no growing backlog.
"""

from __future__ import annotations

import copy
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import run as run_module  # noqa: E402
from harness import loading  # noqa: E402
from harness.checks import float8  # noqa: E402


def _gap_stats(serve, cfg, w, good, seed, mix, control):
    sample = serve.sample_for_check(good, seed, int(mix["check_requests"]))
    gaps = np.concatenate([
        serve.gaps_of(cfg, w, o, control=float8 if control else None)
        for o in sample
    ])
    return {
        "tokens": int(gaps.size), "widest": float(gaps.max()),
        "mean": float(gaps.mean()), "nonzero_share": float((gaps > 0).mean()),
        "p99": float(np.percentile(gaps, 99)),
    }


def main(argv) -> int:
    cell_name, mode, seconds = argv[0], argv[1], float(argv[2])
    cell, cfg, workload = run_module.load_cell(cell_name)
    mix = workload["traffic"]
    run_module.open_devices(int(cell["chips"]))
    from znicz_tpu.core import backend

    backend.enable_compile_cache()
    serve = loading.load_module("drivers", "serve_open_loop")
    min_prompt = int(mix["prompt_tokens"].get("min", 1))

    if mode == "gaps":
        for seed in (int(s) for s in argv[3:]):
            server = serve.Server(cfg, seed, float(mix["deadline_s"]))
            try:
                server.warm(np.random.default_rng(seed + 2), min_prompt)
                measured = serve.measure(server, mix, seed, seconds)
            finally:
                server.close()
            summary = serve.summarise(measured, seconds, float(mix["deadline_s"]))
            for control in (False, True):
                print(json.dumps({
                    "cell": cell_name, "seed": seed,
                    "side": "control" if control else "program",
                    **_gap_stats(serve, cfg, server.weights, summary["good"],
                                 seed, mix, control),
                    **summary["metrics"], "failed": summary["failed"],
                }), flush=True)
            del server
        return 0

    seed, rates = int(argv[3]), [float(r) for r in argv[4:]]
    server = serve.Server(cfg, seed, float(mix["deadline_s"]))
    try:
        server.warm(np.random.default_rng(seed + 2), min_prompt)
        for rate in rates:
            swept = copy.deepcopy(mix)
            swept["arrivals"]["rate_per_s"] = rate
            measured = serve.measure(server, swept, seed, seconds)
            summary = serve.summarise(measured, seconds, float(mix["deadline_s"]))
            good = summary["good"]
            # a backlog that grows shows as time to first token rising
            # through the window: compare its first and last thirds
            third = seconds / 3
            early = [o.first_s - o.planned.due_s for o in good if o.planned.due_s < third]
            late = [o.first_s - o.planned.due_s for o in good if o.planned.due_s >= 2 * third]
            age = measured["delta"].hist("znicz_serve_frontdoor_queue_age_seconds")
            print(json.dumps({
                "rate": rate, "attempted": summary["attempted"],
                "failed": summary["failed"], **summary["metrics"],
                "ttft_median_first_third_ms": 1e3 * float(np.median(early)) if early else None,
                "ttft_median_last_third_ms": 1e3 * float(np.median(late)) if late else None,
                "queue_age_mean_ms": 1e3 * age["sum"] / age["count"] if age else None,
                "drain_s": max((o.end_s for o in good), default=0.0) - seconds,
            }), flush=True)
    finally:
        server.close()
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
