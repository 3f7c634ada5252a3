"""Read, on the chip, what the limits of a ``serve_latent_moe`` cell are
set from, or sweep its arrival rate for the knee.  One process.

    python3 benchmarks/tools/calibrate_axk1.py <cell> gaps <seconds> <seed> [<seed> ...]
    python3 benchmarks/tools/calibrate_axk1.py <cell> sweep <seconds> <seed> <rate> [<rate> ...]

``gaps``: per seed a new server (weights from the seed), a window of
<seconds> at the cell's own load, then the served-token logit gaps of the
sampled requests for the program and for both controls (float8 inputs to
every product; the cached rows rounded to float8).  Every run of the cell
prints the program's own two readings beside their limits, so the
readings over many seeds come from ordinary runs; this mode is for the
controls.  ``sweep``: one server, one window per rate; the knee is the
highest rate at which the tokens delivered keep up with those offered
and time to first token does not rise from the window's first third to
its last.
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

SIDES = {
    "program": {},
    "control_float8_products": {"control": float8},
    "control_float8_cache": {"cache_control": float8},
}


def _gap_stats(serve, cfg, w, good, seed, mix, **control):
    sample = serve.sample_for_check(good, seed, int(mix["check_requests"]))
    pad_to = max(len(o.planned.prompt) + len(o.tokens) for o in sample)
    gaps = np.concatenate(
        [serve.gaps_of(cfg, w, o, pad_to, **control) for o in sample]
    )
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
    serve = loading.load_module("drivers", "serve_latent_moe")
    min_prompt = int(mix["prompt_tokens"].get("min", 1))
    deadline_s = float(mix["deadline_s"])

    if mode == "gaps":
        for seed in (int(s) for s in argv[3:]):
            server = serve.Server(cfg, seed, deadline_s)
            try:
                server.warm(np.random.default_rng(seed + 2), min_prompt)
                server.prime_prefix(mix, seed)
                measured = serve.measure(server, mix, seed, seconds)
            finally:
                server.close()
            server.release()
            summary = serve.summarise(measured, seconds, deadline_s)
            for side, control in SIDES.items():
                print(json.dumps({
                    "cell": cell_name, "seed": seed, "side": side,
                    **_gap_stats(serve, cfg, server.weights, summary["good"],
                                 seed, mix, **control),
                    **summary["metrics"], "failed": summary["failed"],
                }), flush=True)
            del server
        return 0

    seed, rates = int(argv[3]), [float(r) for r in argv[4:]]
    server = serve.Server(cfg, seed, deadline_s)
    try:
        server.warm(np.random.default_rng(seed + 2), min_prompt)
        server.prime_prefix(mix, seed)
        for rate in rates:
            swept = copy.deepcopy(mix)
            swept["arrivals"]["rate_per_s"] = rate
            measured = serve.measure(server, swept, seed, seconds)
            summary = serve.summarise(measured, seconds, deadline_s)
            good = summary["good"]
            third = seconds / 3
            early = [o.first_s - o.planned.due_s for o in good if o.planned.due_s < third]
            late = [o.first_s - o.planned.due_s for o in good if o.planned.due_s >= 2 * third]
            offered = sum(o.planned.max_new_tokens for o in measured["outcomes"])
            age = measured["delta"].hist("znicz_serve_frontdoor_queue_age_seconds")
            pairs = measured["delta"].value("znicz_serve_moe_pairs_total", phase="decode")
            layer_steps = measured["delta"].value(
                "znicz_serve_moe_layer_steps_total", phase="decode"
            )
            print(json.dumps({
                "rate": rate, "attempted": summary["attempted"],
                "failed": summary["failed"], **summary["metrics"],
                "offered_tokens_per_s": offered / seconds,
                "ttft_median_first_third_ms": 1e3 * float(np.median(early)) if early else None,
                "ttft_median_last_third_ms": 1e3 * float(np.median(late)) if late else None,
                "queue_age_mean_ms": 1e3 * age["sum"] / age["count"] if age else None,
                "pairs_per_held_expert": (
                    pairs / layer_steps / cfg["n_routed_experts"] if layer_steps else None
                ),
                "drain_s": max((o.end_s for o in good), default=0.0) - seconds,
            }), flush=True)
    finally:
        server.close()
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
