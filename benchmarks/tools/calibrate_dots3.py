"""Read, on the chip, what the limits of a ``serve_sparse_latent`` cell are
set from, or sweep its arrival rate for the knee.  One process.

    python3 benchmarks/tools/calibrate_dots3.py <cell> gaps <seconds> <seed> [<seed> ...]
    python3 benchmarks/tools/calibrate_dots3.py <cell> program <seconds> <seed> [<seed> ...]
    python3 benchmarks/tools/calibrate_dots3.py <cell> sweep <seconds> <seed> <rate> [<rate> ...] [gaps]

``gaps``: per seed a new server (weights from the seed), a window of
<seconds> at the cell's own load, then the served-token logit gaps of the
sampled requests for the program and for the four controls (float8 inputs
to every product; every key attended; the most recent keys instead of the
best-scored; half as many keys kept), and the share of selected keys a
bfloat16 indexer shares with the reference's.  ``program``: the same for
the program alone (the controls judge the reference's own tokens, so a
seed's controls cost four more passes of the reference and say nothing of
the program).  ``sweep``: one server, one
window per rate; the knee is the highest rate at which the tokens
delivered keep within 5 % of those offered and the median time to first
token of the window's last third does not exceed that of its first by
half.  With a trailing ``gaps`` the last window's requests are then read
like a ``gaps`` seed's, on the same weights.
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
from harness import loading  # noqa: E402


def _gap_lines(serve, cell_name, cfg, w, summary, seed, mix, controls=True):
    sample = serve.sample_for_check(summary["good"], seed, int(mix["check_requests"]))
    pad_to = max(len(o.planned.prompt) + len(o.tokens) for o in sample)
    rows_pad_to = max(len(o.tokens) for o in sample)
    sides = [("program", None)] + (sorted(serve.CONTROLS.items()) if controls else [])
    for side, control in sides:
        t0 = time.perf_counter()
        read = [
            serve.read_request(cfg, w, o, pad_to, rows_pad_to, control=control)
            for o in sample
        ]
        gaps = np.concatenate([g for g, _ in read])
        shared = np.concatenate([s[: len(g)] for g, s in read])
        print(json.dumps({
            "cell": cell_name, "seed": seed, "side": side,
            "longest": pad_to, "tokens": int(gaps.size),
            "widest": float(gaps.max()), "mean": float(gaps.mean()),
            "nonzero_share": float((gaps > 0).mean()),
            "p99": float(np.percentile(gaps, 99)),
            "selected_keys_not_shared_mean": float(1.0 - shared.mean()),
            "selected_keys_not_shared_most": float(1.0 - shared.min()),
            "read_s": time.perf_counter() - t0,
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
    phases = delta.phases("znicz_serve_phase_seconds")
    steps = delta.value("znicz_serve_decode_steps_total")
    scored = delta.value("znicz_serve_sparse_keys_scored_total", phase="decode")
    selected = delta.value("znicz_serve_sparse_keys_selected_total", phase="decode")
    return {
        "rate": rate, "attempted": summary["attempted"],
        "failed": summary["failed"], **summary["metrics"],
        **summary["client"],
        "offered_tokens_per_s": offered / seconds,
        "ttft_median_first_third_ms": 1e3 * float(np.median(early)) if early else None,
        "ttft_median_last_third_ms": 1e3 * float(np.median(late)) if late else None,
        "queue_age_mean_ms": 1e3 * age["sum"] / age["count"] if age else None,
        "preemptions": delta.value("znicz_serve_preemptions_total"),
        "prefill_chunks": delta.value("znicz_serve_prefill_chunks_total"),
        "phase_ms": {
            name: 1e3 * p["sum"] / p["count"] for name, p in phases.items()
        },
        "decode_steps": steps,
        "keys_scored_per_step": scored / steps if steps and scored else None,
        "keys_selected_per_step": selected / steps if steps and selected else None,
        "bytes_per_resident_token": (
            density["sum"] / density["count"] if density else None
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
    serve = loading.load_module("drivers", workload["driver"])
    min_prompt = int(mix["prompt_tokens"].get("min", 1))
    deadline_s = float(mix["deadline_s"])

    def peak():
        stats = devices[0].memory_stats() or {}
        return stats.get("peak_bytes_in_use", 0) + stats.get("peak_bytes_reserved", 0)

    if mode in ("gaps", "program"):
        for seed in (int(s) for s in argv[3:]):
            server = serve.Server(cfg, seed, deadline_s)
            try:
                server.warm(np.random.default_rng(seed + 2), min_prompt)
                measured = serve.measure(server, mix, seed, seconds)
            finally:
                server.close()
            server.release()
            summary = serve.summarise(measured, seconds, deadline_s)
            _gap_lines(
                serve, cell_name, cfg, server.weights, summary, seed, mix,
                controls=mode == "gaps",
            )
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
