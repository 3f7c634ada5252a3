"""Span tracer: nested host-side spans as Chrome trace-event JSONL.

Complements :func:`znicz_tpu.utils.profiling.trace` (the jax profiler's
device capture): this tracer records the HOST side — admit/decode
chunks, training phases, loader waits — as Chrome trace events that
Perfetto (https://ui.perfetto.dev) renders on a timeline.  Once jax has
been imported, every span also enters ``jax.profiler.TraceAnnotation``,
whether this tracer records or not: the annotation is inert outside a
profiler session, and inside one (``/debug/profile``,
``utils.profiling.trace``, an operator's own ``jax.profiler``) it puts
the span on the device trace's clock, on whichever thread entered it.

Events are complete spans (``"ph": "X"``) with microsecond ``ts``/
``dur`` relative to :meth:`Tracer.start`, one JSON object per line when
streaming to a file (Perfetto's JSON importer accepts concatenated
objects; the array wrapper is optional in the trace-event format).
While the tracer is not recording a span emits no event and costs the
annotation alone, so instrumentation stays in place permanently.
"""

from __future__ import annotations

import contextlib
import json
import logging
import os
import sys
import threading
import time
from collections import Counter
from typing import Dict, Iterator, List, Optional

logger = logging.getLogger(__name__)

_UNSET = object()

# default size cap for a STREAMED trace file: on a long-running server
# the stream is otherwise unbounded (the in-memory buffer is capped,
# the file deliberately is not truncated — so it must rotate instead)
TRACE_FILE_MAX_BYTES = 256 * 1024 * 1024


class Tracer:
    """Nested host-span recorder with Chrome trace-event export.

    Usage::

        tracer = observability.get_tracer()
        tracer.start(path="/tmp/run.trace.jsonl")  # stream as JSONL
        with tracer.span("epoch", n=3):
            with tracer.span("dispatch/train"):
                ...
        events = tracer.stop()
    """

    def __init__(self, *, max_events: int = 1_000_000):
        self._lock = threading.Lock()
        self._local = threading.local()
        self._events: List[dict] = []
        self._recording = False
        self._file = None
        self._path: Optional[str] = None
        self._file_bytes = 0
        self._max_file_bytes = 0
        self._t0 = time.perf_counter()
        # wall-clock twin of _t0: the collector rebases instances onto
        # one shared timeline by epoch difference (cross-process clock
        # alignment is exactly what wall clock is for)
        self.epoch_us = time.time() * 1e6
        self._max_events = max_events
        self.dropped = 0
        self.rotations = 0
        self._annotation = _UNSET
        # fleet tracing: the default instance tag every emitted event
        # carries (pid=instance in the collector's merged view), and
        # bounded sinks a TracePusher drains span batches from
        self.instance: Optional[str] = None
        self._sinks: List = []

    @property
    def recording(self) -> bool:
        return self._recording

    def set_instance(self, instance: Optional[str]) -> None:
        """Default ``instance`` tag stamped into every emitted event's
        args (explicit per-span ``instance=...`` args win).  The
        fleet trace collector groups the merged timeline by this tag —
        one process serving several logical instances (an in-process
        test fleet) tags per-span instead."""
        with self._lock:
            self.instance = instance

    def add_sink(self, maxlen: int = 65536):
        """Register a BOUNDED event sink (a deque): every emitted event
        is appended, oldest dropped past ``maxlen`` — the TracePusher's
        intake.  Returns the deque; detach with :meth:`remove_sink`."""
        from collections import deque

        q = deque(maxlen=int(maxlen))
        with self._lock:
            self._sinks.append(q)
        return q

    def remove_sink(self, q) -> None:
        with self._lock:
            if q in self._sinks:
                self._sinks.remove(q)

    def ensure_recording(self) -> bool:
        """Start a buffer-only recording window if none is active (the
        front door's collector wiring calls this so spans flow without
        the operator having to start the tracer by hand).  True when
        THIS call started it."""
        with self._lock:
            if self._recording:
                return False
        try:
            self.start()
        except RuntimeError:
            return False  # lost the race: someone else just started it
        return True

    def start(
        self,
        path: Optional[str] = None,
        *,
        max_file_bytes: Optional[int] = TRACE_FILE_MAX_BYTES,
    ) -> None:
        """Begin recording (optionally streaming each event to ``path``
        as one JSON object per line).  Clears any previous events.

        The streamed file is SIZE-CAPPED at ``max_file_bytes``
        (``None``/``0`` disables): when a write would cross the cap the
        file rotates — the current file becomes ``<path>.1``
        (overwriting any previous rotation) and streaming continues
        into a fresh ``<path>`` — so a long-running server keeps at
        most ~two caps of trace on disk, newest window always in
        ``<path>``."""
        with self._lock:
            if self._recording:
                raise RuntimeError("tracer is already recording")
            self._events = []
            self.dropped = 0
            self.rotations = 0
            self._t0 = time.perf_counter()
            self.epoch_us = time.time() * 1e6  # wall twin of _t0
            self._path = path
            # znicz-check: disable=ZNC016 -- one-time start(): the
            # handle IS the lock-guarded state; a local open-for-write
            # is bounded and racing it against span() would lose events
            self._file = (
                open(path, "w")  # znicz-check: disable=ZNC016
                if path
                else None
            )
            self._file_bytes = 0
            self._max_file_bytes = int(max_file_bytes or 0)
            self._recording = True

    def stop(self) -> List[dict]:
        """Stop recording; returns (and keeps) the event list.  When the
        in-memory buffer overflowed, says so — the streamed JSONL file
        (if any) holds every event since its last rotation (older
        generations beyond ``<path>.1`` rotate away)."""
        with self._lock:
            self._recording = False
            if self._file is not None:
                self._file.close()
                self._file = None
            if self.dropped:
                logger.warning(
                    "tracer buffer dropped %d events past max_events=%d;"
                    " the streamed JSONL file (if any) is complete back"
                    " to its last rotation (%d rotations)",
                    self.dropped,
                    self._max_events,
                    self.rotations,
                )
            return list(self._events)

    def events(self) -> List[dict]:
        with self._lock:
            return list(self._events)

    def span_counts(self) -> Counter:
        """Span-name -> completed-span count (the acceptance
        cross-check: N requests => N ``serve/admit`` spans)."""
        return Counter(
            e["name"] for e in self.events() if e.get("ph") == "X"
        )

    def write_jsonl(self, path: str) -> None:
        """Dump the buffered events, one JSON object per line."""
        with self._lock:
            events = list(self._events)
        with open(path, "w") as f:
            for ev in events:
                f.write(json.dumps(ev, separators=(",", ":")) + "\n")

    # -- emission ----------------------------------------------------------

    def _emit(self, ev: dict) -> None:
        with self._lock:
            if not self._recording:
                return  # span outlived a stop(): drop, don't corrupt
            if self.instance is not None:
                # default instance tag (explicit per-span args win):
                # the fleet collector's pid=instance grouping key
                args = ev.setdefault("args", {})
                args.setdefault("instance", self.instance)
            for q in self._sinks:
                q.append(ev)  # bounded: deque maxlen drops the oldest
            # the file streams EVERY event (disk is the durable record);
            # only the in-memory buffer is capped — the file instead
            # ROTATES at max_file_bytes so a long-running server's
            # trace stays bounded without losing the newest window
            if self._file is not None:
                # ensure_ascii JSON is pure ASCII, so len(line) IS the
                # on-disk byte count the rotation cap accounts against
                line = json.dumps(ev, separators=(",", ":")) + "\n"
                if (
                    self._max_file_bytes
                    and self._file_bytes
                    and self._file_bytes + len(line) > self._max_file_bytes
                ):
                    # znicz-check: disable=ZNC016 -- rotation must be
                    # atomic with the stream (the handle is the guarded
                    # state); rename+reopen on a local FS is bounded and
                    # fires once per max_file_bytes of trace
                    self._rotate_locked()  # znicz-check: disable=ZNC016
                if self._file is not None:
                    # a doubly-failed rotation (rename AND reopen) drops
                    # the stream: memory-buffer-only from here
                    self._file.write(line)
                    self._file_bytes += len(line)
            if len(self._events) >= self._max_events:
                self.dropped += 1
                return
            self._events.append(ev)

    def _rotate_locked(self) -> None:
        """Close the streamed file, shift it to ``<path>.1`` and reopen
        ``<path>`` (lock held by the caller).  A failed rename keeps
        streaming into the grown file — rotation is best-effort, the
        trace must never take the server down."""
        try:
            self._file.close()
            os.replace(self._path, self._path + ".1")
            self._file = open(self._path, "w")
            self._file_bytes = 0
            self.rotations += 1
        except OSError:
            logger.warning(
                "trace rotation of %s failed; stream continues uncapped",
                self._path, exc_info=True,
            )
            self._max_file_bytes = 0
            if self._file.closed:  # reopen in append: keep streaming
                try:
                    self._file = open(self._path, "a")
                except OSError:
                    # the path itself is gone (dir deleted, EROFS):
                    # degrade to the in-memory buffer — the trace must
                    # never take the instrumented thread down
                    logger.warning(
                        "trace stream %s lost; buffering in memory only",
                        self._path, exc_info=True,
                    )
                    self._file = None

    def _annotation_cls(self):
        """``jax.profiler.TraceAnnotation`` once jax has been imported,
        else None — this module never imports jax first, so it stays
        jax-free for hosts with no accelerator stack (and a process
        that has not touched jax has no profiler session to feed)."""
        if self._annotation is _UNSET:
            if "jax" not in sys.modules:
                return None  # not resolved yet: ask again next span
            try:
                from jax.profiler import TraceAnnotation

                self._annotation = TraceAnnotation
            except Exception:
                logger.debug(
                    "jax TraceAnnotation unavailable; host spans only",
                    exc_info=True,
                )
                self._annotation = None
        return self._annotation

    @contextlib.contextmanager
    def span(self, name: str, **args) -> Iterator[None]:
        """One nested host span; ``args`` land in the event's ``args``.

        Recording or not, the span enters
        ``jax.profiler.TraceAnnotation(name)`` so a profiler session
        opened by anyone (``profiling.trace``, ``/debug/profile``) carries
        the program's span names; the Chrome trace event is emitted
        only inside a recording window."""
        ann = self._annotation_cls()
        if not self._recording:
            if ann is None:
                yield
            else:
                with ann(name):
                    yield
            return
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        parent = stack[-1] if stack else None
        stack.append(name)
        ctx = ann(name) if ann is not None else contextlib.nullcontext()
        t0 = time.perf_counter()
        try:
            with ctx:
                yield
        finally:
            dur = time.perf_counter() - t0
            stack.pop()
            a: Dict[str, object] = dict(args)
            if parent is not None:
                a["parent"] = parent
            ev = {
                "name": name,
                "ph": "X",
                "cat": "host",
                "ts": round((t0 - self._t0) * 1e6, 3),
                "dur": round(dur * 1e6, 3),
                "pid": os.getpid(),
                "tid": threading.get_ident(),
            }
            if a:
                ev["args"] = a
            self._emit(ev)

    def instant(self, name: str, **args) -> None:
        """A zero-duration marker event (``"ph": "i"``)."""
        if not self._recording:
            return
        ev = {
            "name": name,
            "ph": "i",
            "s": "t",
            "cat": "host",
            "ts": round((time.perf_counter() - self._t0) * 1e6, 3),
            "pid": os.getpid(),
            "tid": threading.get_ident(),
        }
        if args:
            ev["args"] = args
        self._emit(ev)


_DEFAULT = Tracer()


def get_tracer() -> Tracer:
    """The process-wide default tracer every subsystem's spans feed."""
    return _DEFAULT


def span(name: str, **args):
    return _DEFAULT.span(name, **args)


def instant(name: str, **args) -> None:
    _DEFAULT.instant(name, **args)
