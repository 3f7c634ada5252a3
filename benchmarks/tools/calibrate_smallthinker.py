"""Read, on the chip, what the limits of a ``serve_window_moe`` cell are
set from, or sweep its arrival rate for the knee.  One process.

    python3 benchmarks/tools/calibrate_smallthinker.py <cell> gaps <seconds> <seed> [<seed> ...]
    python3 benchmarks/tools/calibrate_smallthinker.py <cell> sweep <seconds> <seed> <rate> [<rate> ...] [gaps]

``gaps``: per seed a new server (weights from the seed), a window of
<seconds> at the cell's own load, then the served-token logit gaps of the
sampled requests for the program and for both controls (float8 inputs to
every product; the cached K and V rounded to float8).  ``sweep``: one
server, one window per rate; the knee is the highest rate at which the
tokens delivered keep within 5 % of those offered and the median time to
first token of the window's last third does not exceed that of its first by
half.  With a trailing ``gaps`` the last window's requests are then read
like a ``gaps`` seed's, on the same weights.
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


def _gap_lines(serve, cell_name, cfg, w, summary, seed, mix):
    sample = serve.sample_for_check(summary["good"], seed, int(mix["check_requests"]))
    pad_to = max(len(o.planned.prompt) + len(o.tokens) for o in sample)
    rows_pad_to = max(len(o.tokens) for o in sample)
    for side, control in SIDES.items():
        gaps = np.concatenate(
            [serve.gaps_of(cfg, w, o, pad_to, rows_pad_to, **control) for o in sample]
        )
        print(json.dumps({
            "cell": cell_name, "seed": seed, "side": side,
            "longest": pad_to, "tokens": int(gaps.size),
            "widest": float(gaps.max()), "mean": float(gaps.mean()),
            "nonzero_share": float((gaps > 0).mean()),
            "p99": float(np.percentile(gaps, 99)),
            **summary["metrics"], "failed": summary["failed"],
        }), flush=True)


def _sweep_line(cfg, rate, seconds, measured, summary):
    good, delta = summary["good"], measured["delta"]
    third = seconds / 3
    early = [o.first_s - o.planned.due_s for o in good if o.planned.due_s < third]
    late = [o.first_s - o.planned.due_s for o in good if o.planned.due_s >= 2 * third]
    offered = sum(o.planned.max_new_tokens for o in measured["outcomes"])
    age = delta.hist("znicz_serve_frontdoor_queue_age_seconds")
    density = delta.hist("znicz_serve_cache_bytes_per_resident_token")
    idle = delta.value("znicz_serve_moe_idle_experts_total", phase="decode")
    layer_steps = delta.value("znicz_serve_moe_layer_steps_total", phase="decode")
    return {
        "rate": rate, "attempted": summary["attempted"],
        "failed": summary["failed"], **summary["metrics"],
        **summary["client"],
        "offered_tokens_per_s": offered / seconds,
        "ttft_median_first_third_ms": 1e3 * float(np.median(early)) if early else None,
        "ttft_median_last_third_ms": 1e3 * float(np.median(late)) if late else None,
        "queue_age_mean_ms": 1e3 * age["sum"] / age["count"] if age else None,
        "preemptions": delta.value("znicz_serve_preemptions_total"),
        "window_blocks_released": delta.value(
            "znicz_serve_window_blocks_released_total"
        ),
        "bytes_per_resident_token": (
            density["sum"] / density["count"] if density else None
        ),
        "experts_hit_per_layer": (
            cfg["moe_num_primary_experts"] - idle / layer_steps
            if layer_steps else None
        ),
        "drain_s": max((o.end_s for o in good), default=0.0) - seconds,
    }


def main(argv) -> int:
    cell_name, mode, seconds = argv[0], argv[1], float(argv[2])
    cell, cfg, workload = run_module.load_cell(cell_name)
    mix = workload["traffic"]
    devices = run_module.open_devices(int(cell["chips"]))
    from znicz_tpu.core import backend

    backend.enable_compile_cache()
    serve = loading.load_module("drivers", "serve_window_moe")
    min_prompt = int(mix["prompt_tokens"].get("min", 1))
    deadline_s = float(mix["deadline_s"])

    def peak():
        stats = devices[0].memory_stats() or {}
        return stats.get("peak_bytes_in_use", 0) + stats.get("peak_bytes_reserved", 0)

    if mode == "gaps":
        for seed in (int(s) for s in argv[3:]):
            server = serve.Server(cfg, seed, deadline_s)
            try:
                server.warm(np.random.default_rng(seed + 2), min_prompt)
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
        server.warm(np.random.default_rng(seed + 2), min_prompt)
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
