"""Read, on the chip, what the limits of the ``serve_gated_window_moe`` cell
are set from, or sweep its arrival rate for the knee.  One process.

    python3 benchmarks/tools/calibrate_laguna.py <cell> gaps <seconds> <seed> [<seed> ...]
    python3 benchmarks/tools/calibrate_laguna.py <cell> sweep <seconds> <seed> <rate> [<rate> ...] [gaps]

``gaps``: per seed a new server (weights from the seed, the shared prefix
primed), a window of <seconds> at the cell's own load, then the
served-token logit gaps of the sampled requests for the program and for
the five controls of ``drivers/serve_gated_window_moe.CONTROLS`` (each
computes the shared prefix's state anew under it).  ``sweep``: one primed
server, one ramp and window a rate, ``tools/calibrate_dots3.py``'s line for
each (the knee is read as there) with the prefix cache's counters beside
it; with a trailing ``gaps`` the last window's requests are then read like
a ``gaps`` seed's, on the same weights.
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
        gaps = reading.gaps(control)
        print(json.dumps({
            "cell": cell_name, "seed": seed, "side": side,
            "tokens": int(gaps.size), "widest": float(gaps.max()),
            "mean": float(gaps.mean()),
            "nonzero_share": float((gaps > 0).mean()),
            "p99": float(np.percentile(gaps, 99)),
            "prefix_state_s": reading.state_s,
            "read_s": time.perf_counter() - t0,
            **summary["metrics"], "failed": summary["failed"],
        }), flush=True)


def _cache_line(delta):
    def value(name, **labels):
        return delta.value(name, **labels)

    return {
        "prompt_tokens": value("znicz_serve_prompt_tokens_total"),
        "cached_tokens": value("znicz_serve_prefix_cached_tokens_total"),
        "hit_requests": value("znicz_serve_prefix_hit_requests_total"),
        "mapped_global": value("znicz_serve_prefix_blocks_mapped_total", kind="global"),
        "mapped_window": value("znicz_serve_prefix_blocks_mapped_total", kind="window"),
        "chain_cut": value("znicz_serve_prefix_chain_cut_total"),
        "evictions": value("znicz_serve_prefix_evictions_total"),
        "compiles": value("znicz_serve_compiles_total"),
        "idle_experts": value("znicz_serve_moe_idle_experts_total", phase="decode"),
        "moe_layer_steps": value("znicz_serve_moe_layer_steps_total", phase="decode"),
        "rows_global": value("znicz_serve_decode_cached_rows_total", kind="global"),
        "rows_window": value("znicz_serve_decode_cached_rows_total", kind="window"),
    }


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
    t0 = time.perf_counter()
    server = serve.Server(cfg, seed, deadline_s)
    try:
        t1 = time.perf_counter()
        server.prime(mix, seed)
        print(json.dumps({
            "serving": {k: v for k, v in cfg["serving"].items() if k != "why"},
            "server_s": t1 - t0, "primed_s": time.perf_counter() - t1,
            "memory_peak_bytes": peak(),
        }), flush=True)
        for rate in rates:
            swept = copy.deepcopy(mix)
            swept["arrivals"]["rate_per_s"] = rate
            measured = serve.measure(server, swept, seed, seconds)
            summary = serve.summarise(measured, seconds, deadline_s)
            print(json.dumps(dict(
                _sweep_line(cfg, rate, seconds, measured, summary),
                memory_peak_bytes=peak(), **_cache_line(measured["delta"]),
            )), flush=True)
    finally:
        server.close()
    if then_gaps:
        server.release()
        _gap_lines(serve, cell_name, cfg, server.weights, summary, seed, swept)
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
