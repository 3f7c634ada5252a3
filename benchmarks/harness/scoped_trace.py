"""Device time by named scope, from the same ``.xplane.pb`` that
``trace/reduce.py`` reads.

``trace/reduce.py`` names an "XLA Ops" event by its HLO instruction
(``fusion.32``), which says nothing about which part of the model it
belongs to.  The program marks its parts with ``jax.named_scope``; XLA
carries the scope path in every instruction's ``op_name`` metadata
(``jit(_paged_decode_chunk)/while/body/mla_absorbed/dot_general``).  On
this installation the profiler does not hand that on with the event (an
event has its HLO line for a name and its timing for stats: my chip run,
PR 26); it does keep every traced program's ``HloProto`` in the
``/host:metadata`` plane, which ``jax.profiler.ProfileData`` does not
expose.  So this file reads the protobuf wire format itself (field numbers
of ``xplane.proto`` and ``hlo.proto``, written beside each use): program
-> {instruction: op_name} from the metadata plane, then device 0's
operations, each assigned to the program execution ("XLA Modules" event)
that contains it, summed by (program, marker).  An operation whose
``op_name`` names no marker is left out; a path that names several counts
under the innermost.  A trace without the metadata gives empty tables,
never an error.

``ScopedCapture`` is ``harness.profile.Capture`` that also keeps this
table (``scoped``) when the trace file is read."""

from __future__ import annotations

import bisect
import glob
import os
import re
import statistics
from typing import Dict, Iterable, Iterator, Optional, Tuple

from harness.loading import load_module
from harness.profile import Capture

_INSTRUCTION = re.compile(r"^%?([\w.\-]+)")


def _varint(buf: bytes, at: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[at]
        at += 1
        value |= (byte & 0x7F) << shift
        shift += 7
        if not byte & 0x80:
            return value, at


def _fields(buf: bytes) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one message: ints for varints, bytes for
    length-delimited and fixed-width fields."""
    at, end = 0, len(buf)
    while at < end:
        key, at = _varint(buf, at)
        wire = key & 7
        if wire == 0:
            value, at = _varint(buf, at)
        elif wire == 2:
            size, at = _varint(buf, at)
            value, at = buf[at:at + size], at + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, at = buf[at:at + size], at + size
        else:
            raise ValueError(f"wire type {wire}")
        yield key >> 3, value


def _first(buf: bytes, number: int, default=None):
    for field, value in _fields(buf):
        if field == number:
            return value
    return default


def _program(module_event_name: str) -> str:
    """``jit_f(1234)`` -> ``jit_f``."""
    return module_event_name.split("(", 1)[0]


def instruction_scopes(metadata_plane: bytes) -> Dict[str, Dict[str, str]]:
    """``{program: {instruction: op_name}}`` from the ``/host:metadata``
    plane: XPlane.event_metadata (4) is a map entry {2: XEventMetadata};
    XEventMetadata {2: name, 5: stats}; the stat's bytes_value (6) is an
    HloProto {1: HloModuleProto {3: computations {2: instructions {1: name,
    7: OpMetadata {2: op_name}}}}}."""
    out: Dict[str, Dict[str, str]] = {}
    for field, entry in _fields(metadata_plane):
        if field != 4:
            continue
        event_metadata = _first(entry, 2, b"")
        name = _first(event_metadata, 2, b"").decode()
        table = out.setdefault(_program(name), {})
        for f, stat in _fields(event_metadata):
            proto = _first(stat, 6) if f == 5 else None
            module = _first(proto, 1) if proto else None
            if not module:
                continue
            for cf, computation in _fields(module):
                if cf != 3:
                    continue
                for nf, instruction in _fields(computation):
                    if nf != 2:
                        continue
                    op_metadata = _first(instruction, 7)
                    op_name = _first(op_metadata, 2) if op_metadata else None
                    if op_name:
                        table[_first(instruction, 1, b"").decode()] = op_name.decode()
    return out


def _lines(plane: bytes) -> Dict[str, list]:
    """``{line name: [(event name, start ps, duration ps)]}`` of a plane:
    XPlane {3: lines, 4: event_metadata}; XLine {2: name, 3: timestamp_ns,
    4: events}; XEvent {1: metadata_id, 2: offset_ps, 3: duration_ps}."""
    names = {}
    for field, entry in _fields(plane):
        if field == 4:
            event_metadata = _first(entry, 2, b"")
            names[_first(event_metadata, 1, 0)] = _first(event_metadata, 2, b"").decode()
    out: Dict[str, list] = {}
    for field, line in _fields(plane):
        if field != 3:
            continue
        origin_ps = _first(line, 3, 0) * 1000
        events = out.setdefault(_first(line, 2, b"").decode(), [])
        for f, event in _fields(line):
            if f == 4:
                events.append((
                    names.get(_first(event, 1, 0), ""),
                    origin_ps + _first(event, 2, 0), _first(event, 3, 0),
                ))
    return out


def reduce(path: str, markers: Iterable[str]) -> Dict[str, dict]:
    """``{program: {"whole_executions", "device_s", "steps", "scopes":
    {marker: device seconds}}}`` over device 0, from WHOLE executions
    alone: a trace starts and stops in the middle of some execution, and
    such a stub has the program's name and a part of its time (a decode
    chunk of 696 ms showed as 96 and 355 ms at the two ends of a 2 s
    trace: my chip run, PR 26).  ``device_s`` sums the operations inside
    the whole executions (containers, whose time is their children's, left
    out); ``steps`` is how often most of the program's instructions ran
    there (the mode of their counts): the trips of the program's loop, or
    its executions where it has none.  (Not the largest count: a sort or a
    top-k brings an inner loop whose body runs several times a step.)"""
    reducer = load_module("trace", "reduce")
    markers = list(markers)
    with open(path, "rb") as f:
        space = f.read()
    planes = {
        _first(plane, 2, b"").decode(): plane
        for field, plane in _fields(space) if field == 1  # XSpace.planes
    }
    scopes = instruction_scopes(planes.get("/host:metadata", b""))
    lines = _lines(planes.get("/device:TPU:0", b""))
    ops = lines.get(reducer.OPS_LINE, [])
    if not ops:
        return {}
    first, last = min(s for _, s, _ in ops), max(s + d for _, s, d in ops)
    edge_ps = 1_000_000  # 1 us: a stub begins or ends where the trace does
    modules = sorted(
        (start, start + dur, _program(name))
        for name, start, dur in lines.get(reducer.MODULES_LINE, [])
        if start > first + edge_ps and start + dur < last - edge_ps
    )
    starts = [m[0] for m in modules]
    out: Dict[str, dict] = {}
    counts: Dict[str, Dict[str, int]] = {}
    for _, _, program in modules:
        entry = out.setdefault(program, {
            "whole_executions": 0, "device_s": 0.0, "steps": 0, "scopes": {},
        })
        entry["whole_executions"] += 1
    for name, start, dur in ops:
        if reducer.CONTAINER.match(reducer.op_name(name)):
            continue
        at = bisect.bisect_right(starts, start) - 1
        if at < 0 or start >= modules[at][1]:
            continue
        program = modules[at][2]
        entry = out[program]
        entry["device_s"] += dur / 1e12
        instruction = _INSTRUCTION.match(name).group(1)
        seen = counts.setdefault(program, {})
        seen[instruction] = seen.get(instruction, 0) + 1
        where = scopes.get(program, {}).get(instruction)
        if not where:
            continue
        inner = max(
            ((where.rfind("/" + m), m) for m in markers if "/" + m in where),
            default=None,
        )
        if inner is not None:
            table = entry["scopes"]
            table[inner[1]] = table.get(inner[1], 0.0) + dur / 1e12
    for program, seen in counts.items():
        out[program]["steps"] = statistics.mode(seen.values())
    return out


class ScopedCapture(Capture):
    """A ``Capture`` whose ``scoped`` holds ``reduce``'s table once
    ``reduced`` has been read."""

    def __init__(self, markers, keep_dir: Optional[str] = None):
        super().__init__(keep_dir=keep_dir)
        self._markers = list(markers)
        self.scoped: Optional[dict] = None

    @property
    def reduced(self) -> Optional[dict]:
        if self._dir is not None:
            files = glob.glob(
                os.path.join(self._dir, "plugins", "profile", "*", "*.xplane.pb")
            )
            if files:
                self.scoped = reduce(files[0], self._markers)
        return Capture.reduced.fget(self)
