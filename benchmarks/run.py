"""One process, one cell, once.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell is made of is found by name from ``BENCHMARK.json``:
``configs/<config>.json``, ``workloads/<cell>.json`` (which names its
driver and holds the traffic parameters), ``drivers/<driver>.py`` and, in
a ``--trace 1`` run, ``layer_metrics/<metric>.py`` for every per-layer
metric that lists the cell.  The last line of standard output is the
result, one JSON object; with no TPU (or fewer chips than the cell asks
for) the exit code is not 0 and no result is printed.
"""

from __future__ import annotations

_T_START = __import__("time").perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import time  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
for _p in (BENCH_DIR, os.path.dirname(BENCH_DIR)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from harness import loading  # noqa: E402
from harness.loading import REPO_ROOT  # noqa: E402
from harness.peaks import peaks_for  # noqa: E402


class Run:
    """What a driver is handed: the cell's files, the arguments, and the
    few services every driver needs from the harness."""

    def __init__(self, cell: dict, workload: dict, config: dict, args,
                 devices):
        self.cell = cell["name"]
        self.chips = int(cell["chips"])
        self.config = config
        self.traffic = workload["traffic"]
        self.seed = int(args.seed)
        self.seconds = float(args.seconds)
        self.trace = bool(args.trace)
        self.devices = devices
        # beside the compile cache, inside the checkout; git-ignored
        self.cache_dir = os.path.join(REPO_ROOT, ".bench_cache")
        self.setup_s = None
        self.keep_trace_dir = os.environ.get("ZNICZ_BENCH_KEEP_TRACE")

    def mark_setup_done(self, extra_s: float = 0.0) -> None:
        """Called by the driver at the first measured step or request
        (``extra_s`` ahead of it, where set-up ends in a timed ramp)."""
        self.setup_s = time.perf_counter() - _T_START + extra_s

    def new_capture(self):
        from harness.profile import Capture

        return Capture(keep_dir=self.keep_trace_dir)

    def memory_peak_bytes(self) -> int:
        """Peak bytes on the fullest chip, as the backend reports them:
        the allocator's ``peak_bytes_in_use`` (arrays: state, batches, the
        K/V pool) plus ``peak_bytes_reserved`` (what running programs
        reserve for their temporaries; jax counts the two apart, and the
        free memory it reports is the limit less both).  Drivers read it
        when the window closes, before any reference runs, so it stays
        the program's."""
        peaks = [
            stats.get("peak_bytes_in_use", 0) + stats.get("peak_bytes_reserved", 0)
            for stats in ((d.memory_stats() or {}) for d in self.devices)
        ]
        return int(max(peaks)) if peaks else 0

    @property
    def peaks(self) -> dict:
        return peaks_for(self.devices[0].device_kind)


def _entry(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"BENCHMARK.json has no {what} named {name!r}")


def load_cell(name: str):
    """(cell, configuration, workload) of one ``BENCHMARK.json`` cell."""
    bench = loading.benchmark_json()
    cell = _entry(bench["workloads"], name, "workload")
    config_entry = _entry(bench["configs"], cell["config"], "configuration")
    with open(os.path.join(REPO_ROOT, config_entry["file"])) as f:
        config = json.load(f)
    workload = loading.load_json("workloads", cell["name"] + ".json")
    if workload["traffic_name"] != cell["traffic"]:
        raise SystemExit(
            f"workloads/{cell['name']}.json is traffic "
            f"{workload['traffic_name']!r}, BENCHMARK.json says {cell['traffic']!r}"
        )
    return cell, config, workload


def open_devices(chips: int, require_chip: bool = True):
    """The TPU through the program's own device policy; a machine without
    one (or with too few chips) ends the run before any result."""
    from znicz_tpu.core import backend

    if not require_chip:
        import jax

        return jax.devices()
    try:
        devices = backend.require("tpu")
    except backend.NoAcceleratorError as exc:
        print(f"no accelerator: {exc}", file=sys.stderr)
        raise SystemExit(3)
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(
            f"the cell needs {chips} TPU chip(s); jax found "
            f"{len(devices)} x {devices[0].platform}", file=sys.stderr,
        )
        raise SystemExit(3)
    return devices


def _layer_metrics(bench: dict, cell: str, observations: dict) -> dict:
    out = {}
    for metric in bench["per_layer"]:
        if "workloads" in metric and cell not in metric["workloads"]:
            continue
        reader = loading.load_module("layer_metrics", metric["name"])
        value = reader.read(observations)
        if value is not None:
            out[metric["name"]] = {"value": float(value), "unit": metric["unit"]}
    return out


def main(argv=None, *, require_chip: bool = True) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    bench = loading.benchmark_json()
    cell, config, workload = load_cell(args.workload)
    devices = open_devices(int(cell["chips"]), require_chip)
    import jax

    from znicz_tpu.core import backend

    backend.enable_compile_cache()
    # keep every program, however quick to compile, so that a run after
    # the first finds all of them and set-up stays steady
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    run = Run(cell, workload, config, args, devices[: int(cell["chips"])])
    print(
        f"set-up before the driver (interpreter, imports, device): "
        f"{time.perf_counter() - _T_START:.2f} s", flush=True,
    )
    driver = loading.load_module("drivers", workload["driver"])
    result = driver.run(run)
    if run.setup_s is None:
        raise SystemExit("the driver never marked the end of set-up")

    checks = result["checks"]
    checks.print_all()
    print(f"device memory: {run.devices[0].memory_stats()}", flush=True)
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(run.devices),
        "memory_peak_bytes": int(result["memory_peak_bytes"]),
    }
    line = {
        "correct": bool(checks.correct),
        "attempted": int(result["attempted"]),
        "failed": int(result["failed"]),
    }
    if run.trace:
        observations = result["observations"]
        observations["peaks"] = run.peaks if require_chip else None
        line["metrics"] = _layer_metrics(bench, run.cell, observations)
        trace = observations.get("trace")
        if trace is None:
            raise SystemExit("the traced window holds no device operation")
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        line["breakdown"] = {
            "device_ops": trace["device_ops"], "idle_gaps": trace["idle_gaps"],
        }
    else:
        units = {m["name"]: m["unit"] for m in bench["end_to_end"]}
        metrics = dict(result["metrics"], setup_s=run.setup_s)
        line["metrics"] = {
            name: {"value": float(value), "unit": units[name]}
            for name, value in metrics.items()
        }
    line["device"] = device
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
