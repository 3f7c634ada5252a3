"""From a profiler trace to numbers: device busy time, time per compiled
program and per operation, collective time and its exposed part, and the
idle gaps named by what the host was doing in them.

``load`` turns an ``.xplane.pb`` (read with ``jax.profiler.ProfileData``)
into plain data: ``{"planes": [{"name", "lines": [{"name", "events":
[[name, start_ns, duration_ns], ...]}]}]}``.  ``reduce`` works on that
plain form alone, so it is checked on the recorded trace kept beside this
file (``fixture_trace.json``) without a chip."""

from __future__ import annotations

import re
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
)
# operations that only hold others (their time is their children's)
CONTAINER = re.compile(r"^(while|conditional|call)\b")
# host events that say nothing about what the program was doing
_HOST_NOISE = ("$", "ThreadpoolListener", "end: ")
MIN_HOST_SPAN_NS = 20_000


def load(path: str) -> dict:
    """Only what ``reduce`` reads is kept: the operation and program lines
    of the device planes, and of the host plane the spans of 20 us or
    more that are not interpreter or thread-pool noise (a trace of a few
    seconds holds millions of events that are neither)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        on_device = bool(DEVICE_PLANE.match(plane.name))
        if not on_device and plane.name != HOST_PLANE:
            continue
        lines = []
        for line in plane.lines:
            if on_device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            events = [
                [
                    op_name(e.name) if line.name == OPS_LINE else e.name,
                    float(e.start_ns), float(e.duration_ns),
                ]
                for e in line.events
                if on_device or (
                    e.duration_ns >= MIN_HOST_SPAN_NS
                    and not e.name.startswith(_HOST_NOISE)
                )
            ]
            if events:
                lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def op_name(text: str) -> str:
    """An "XLA Ops" event is named by its whole HLO line, ``%fusion.32 =
    (bf16[1024,27,27,256]{...}) fusion(...)``; keep ``fusion.32`` and the
    shape it produces, which is what tells one fusion from another."""
    head, _, rest = text.partition(" = ")
    shape = rest.split("{", 1)[0].lstrip("(").strip()
    name = head.lstrip("%")
    return f"{name} {shape}" if shape and len(shape) <= 48 else name


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            if hi > out[-1][1]:
                out[-1] = (out[-1][0], hi)
        else:
            out.append((lo, hi))
    return out


def _length(intervals) -> float:
    return sum(hi - lo for lo, hi in intervals)


def _subtract(spans, cover) -> List[Tuple[float, float]]:
    """The parts of ``spans`` (a union) that ``cover`` (a union) leaves."""
    out = []
    for lo, hi in spans:
        at = lo
        for c_lo, c_hi in cover:
            if c_hi <= at:
                continue
            if c_lo >= hi:
                break
            if c_lo > at:
                out.append((at, c_lo))
            at = max(at, c_hi)
            if at >= hi:
                break
        if at < hi:
            out.append((at, hi))
    return out


def _program_name(event_name: str) -> str:
    """``jit_train_acc(1234567)`` -> ``jit_train_acc``."""
    return event_name.split("(", 1)[0]


def _line(plane: dict, name: str) -> List[list]:
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return []


def _collective_spans(ops) -> List[Tuple[float, float]]:
    """Synchronous collectives are their own span; an asynchronous one
    runs from its ``-start`` op to the end of the matching ``-done``."""
    spans, open_starts = [], {}
    for name, start, dur in ops:
        if not COLLECTIVE.match(name):
            continue
        name = name.split(" ", 1)[0]  # drop the shape
        base = re.sub(r"-(start|done)(\.\d+)?$", "", name)
        if re.search(r"-start(\.\d+)?$", name):
            open_starts.setdefault(base, []).append(start)
        elif re.search(r"-done(\.\d+)?$", name):
            begun = open_starts.get(base)
            spans.append((begun.pop(0) if begun else start, start + dur))
        else:
            spans.append((start, start + dur))
    return spans


def reduce(trace: dict, window_ns: Optional[Tuple[float, float]] = None,
           top: int = 10) -> Optional[dict]:
    """Everything the per-layer readers and the result line take from a
    trace; None where no device plane holds an operation.

    Times are seconds.  ``busy_s`` is the union of operation intervals,
    averaged over the device planes; ``programs`` is per compiled program
    (an "XLA Modules" event) executions and device seconds, summed over
    the devices and divided by their number, so a data-parallel step
    counts once; ``collective_s`` and ``collective_exposed_s`` are read on
    the first device alone."""
    devices = sorted(
        (int(DEVICE_PLANE.match(p["name"]).group(1)), p)
        for p in trace["planes"]
        if DEVICE_PLANE.match(p["name"])
    )
    devices = [(i, p) for i, p in devices if _line(p, OPS_LINE)]
    if not devices:
        return None
    all_ops = [e for _, p in devices for e in _line(p, OPS_LINE)]
    if window_ns is None:
        window_ns = (
            min(e[1] for e in all_ops), max(e[1] + e[2] for e in all_ops)
        )
    w_lo, w_hi = window_ns

    def clip(events):
        return [
            [n, max(s, w_lo), min(s + d, w_hi) - max(s, w_lo)]
            for n, s, d in events
            if s + d > w_lo and s < w_hi
        ]

    n_dev = len(devices)
    busy, op_seconds, programs = 0.0, {}, {}
    gaps_by_device = []
    for _, plane in devices:
        ops = clip(_line(plane, OPS_LINE))
        cover = _union([(s, s + d) for _, s, d in ops])
        busy += _length(cover)
        gaps_by_device.append(_subtract([(w_lo, w_hi)], cover))
        for name, _, dur in ops:
            if not CONTAINER.match(name):
                op_seconds[name] = op_seconds.get(name, 0.0) + dur
        for name, _, dur in clip(_line(plane, MODULES_LINE)):
            entry = programs.setdefault(
                _program_name(name), {"executions": 0.0, "device_s": 0.0}
            )
            entry["executions"] += 1.0 / n_dev
            entry["device_s"] += dur / n_dev / 1e9

    first_ops = clip(_line(devices[0][1], OPS_LINE))
    coll = _union(_collective_spans(first_ops))
    compute = _union(
        [(s, s + d) for n, s, d in first_ops if not COLLECTIVE.match(n)]
    )
    exposed = _subtract(coll, compute)

    host_spans = [
        e
        for p in trace["planes"] if p["name"] == HOST_PLANE
        for line in p["lines"] for e in line["events"]
        if e[2] > 0 and not e[0].startswith(_HOST_NOISE)
    ]
    gap_seconds: Dict[str, float] = {}
    for lo, hi in gaps_by_device[0]:
        best, best_key = "(no host span)", (0.0, 0.0)
        for name, s, d in host_spans:
            overlap = min(hi, s + d) - max(lo, s)
            if overlap > 0 and (overlap, -d) > best_key:
                best, best_key = name, (overlap, -d)
        gap_seconds[best] = gap_seconds.get(best, 0.0) + (hi - lo)

    def ranked(table):
        return [
            [name, seconds / 1e9]
            for name, seconds in sorted(table.items(), key=lambda kv: -kv[1])
        ][:top]

    return {
        "window_s": (w_hi - w_lo) / 1e9,
        "busy_s": busy / n_dev / 1e9,
        "n_devices": n_dev,
        "programs": programs,
        "collective_s": _length(coll) / 1e9,
        "collective_exposed_s": _length(exposed) / 1e9,
        "device_ops": ranked(
            {k: v / n_dev for k, v in op_seconds.items()}
        ),
        "idle_gaps": ranked(gap_seconds),
    }
