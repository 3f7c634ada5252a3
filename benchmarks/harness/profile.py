"""Capturing a few seconds of device trace from inside the one process
that holds the chip, and handing the reduction its file."""

from __future__ import annotations

import glob
import os
import shutil
import tempfile
import threading
import time
from typing import Optional

from harness.loading import load_module


class Capture:
    """``start()`` ... ``stop()`` around part of the window; ``reduced``
    afterwards (the file is read when first asked for, outside the
    window).  The trace lands under ``TMPDIR`` and is removed."""

    def __init__(self, keep_dir: Optional[str] = None):
        self._dir = None
        self._keep = keep_dir
        self._reduced: Optional[dict] = None
        self._thread: Optional[threading.Thread] = None
        self._own_tracer = False

    def run_for(self, delay_s: float, seconds: float) -> None:
        """From a thread of its own: wait ``delay_s``, trace for
        ``seconds``, stop.  The caller goes on driving the window and
        calls ``join()`` once it has closed."""

        def timetable():
            time.sleep(delay_s)
            self.start()
            time.sleep(seconds)
            self.stop()

        self._thread = threading.Thread(target=timetable, daemon=True)
        self._thread.start()

    def join(self) -> None:
        if self._thread is not None:
            self._thread.join()
            self._thread = None

    def start(self) -> None:
        import jax

        self._dir = tempfile.mkdtemp(prefix="znicz_bench_trace_")
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # TraceAnnotations, not every call
        options.host_tracer_level = 1
        jax.profiler.start_trace(self._dir, profiler_options=options)
        # the program's own spans enter the trace only while its tracer
        # records; they name what the host did in each idle gap
        from znicz_tpu.observability import get_tracer

        self._own_tracer = get_tracer().ensure_recording()

    def stop(self) -> None:
        import jax

        jax.profiler.stop_trace()
        if self._own_tracer:
            from znicz_tpu.observability import get_tracer

            get_tracer().stop()

    @property
    def reduced(self) -> Optional[dict]:
        if self._dir is None:
            return self._reduced
        try:
            files = glob.glob(
                os.path.join(self._dir, "plugins", "profile", "*", "*.xplane.pb")
            )
            if files:
                print(
                    f"trace file: {os.path.getsize(files[0])} bytes", flush=True
                )
                if self._keep:
                    os.makedirs(self._keep, exist_ok=True)
                    shutil.copy(files[0], self._keep)
                reducer = load_module("trace", "reduce")
                self._reduced = reducer.reduce(reducer.load(files[0]))
        finally:
            shutil.rmtree(self._dir, ignore_errors=True)
            self._dir = None
        return self._reduced
