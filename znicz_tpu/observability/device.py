"""Device & compile telemetry: the program ledger behind /debug/programs.

"Zero new compiled programs" is this repo's core serving invariant, and
the remaining scheduling/kernel ROADMAP rungs all want per-program
timing and memory signals as input — yet until now nothing observed the
device side at all.  This module is that layer:

* **Program ledger** — every TRUE first compile of a serving program
  (the engine's ``prefill``/``paged_chunk``/``spec_verify``/``cow``
  programs, deduped exactly like ``znicz_serve_compiles_total``)
  records one entry: compile wall time and the lowering's cost
  analysis (FLOPs / bytes accessed).  Served at
  ``GET /debug/programs``; the engine-sourced entry count matches the
  engine ledger and ``znicz_serve_compiles_total`` by construction.
* **Metrics** — ``znicz_compile_seconds{kind}`` (histogram),
  ``znicz_program_cost_flops_total{kind}`` /
  ``znicz_program_cost_bytes_total{kind}`` (static per-program costs,
  summed over compiles), ``znicz_device_memory_bytes{kind,device}``
  (live ``memory_stats`` where the backend reports
  them — CPU answers None and the gauges simply stay absent).
* **On-demand device capture** — :func:`capture_profile` runs a
  ``jax.profiler`` trace for N seconds (``POST /debug/profile`` on the
  serving surface), wrapped in a host span so the device capture lines
  up with the host timeline.

Every jax touch is lazy and failure-tolerant: on a host without an
accelerator stack (or a jax without the API) the helpers answer None /
empty and the serving path never notices — the graceful-no-op contract
the ISSUE pins for jax 0.4.37.
"""

from __future__ import annotations

import logging
import tempfile
import threading
import time
from collections import OrderedDict
from typing import Dict, List, Optional

from znicz_tpu.observability.registry import get_registry
from znicz_tpu.observability.tracing import span

logger = logging.getLogger(__name__)

_LOCK = threading.Lock()
# ledger key -> entry dict, insertion (= compile) order
_PROGRAMS: "OrderedDict[str, dict]" = OrderedDict()

# jax.profiler device captures are process-global: one at a time
_PROFILE_LOCK = threading.Lock()
PROFILE_MAX_SECONDS = 30.0

def _m_compile_seconds():
    return get_registry().histogram(
        "znicz_compile_seconds",
        "wall time of true first compiles by program kind",
        ("kind",),
    )


def _m_cost_flops():
    return get_registry().counter(
        "znicz_program_cost_flops_total",
        "cost-analysis FLOPs of compiled programs, summed per kind",
        ("kind",),
    )


def _m_cost_bytes():
    return get_registry().counter(
        "znicz_program_cost_bytes_total",
        "cost-analysis bytes accessed of compiled programs, per kind",
        ("kind",),
    )


def _m_device_memory():
    return get_registry().gauge(
        "znicz_device_memory_bytes",
        "live device memory_stats by kind, where the backend reports them",
        ("kind", "device"),
    )


# -- cost extraction (never raise) -------------------------------------------


def stage_cost(stage) -> Optional[dict]:
    """Normalized ``cost_analysis()`` of a jax ``Lowered``/``Compiled``
    stage: ``{"flops": float|None, "bytes_accessed": float|None}``.
    None when the stage (or this jax) has no cost analysis."""
    try:
        c = stage.cost_analysis()
    except Exception:
        logger.debug("cost_analysis unavailable", exc_info=True)
        return None
    if isinstance(c, (list, tuple)):
        c = c[0] if c else None
    if not isinstance(c, dict):
        return None
    out = {}
    flops = c.get("flops")
    by = c.get("bytes accessed")
    out["flops"] = float(flops) if flops is not None else None
    out["bytes_accessed"] = float(by) if by is not None else None
    return out


def lowered_cost(fn, args, kwargs) -> Optional[dict]:
    """Cost analysis via a throwaway ``fn.lower(...)`` — tracing only,
    no second compile (jit's executable cache is keyed separately from
    AOT lowering, and lowering never touches buffer contents, so this
    is safe even before a donating call).  None on any failure."""
    try:
        lowered = fn.lower(*args, **(kwargs or {}))
    except Exception:
        logger.debug("lowering for cost analysis failed", exc_info=True)
        return None
    return stage_cost(lowered)


# -- the ledger -------------------------------------------------------------


def record_program(
    key,
    compile_s: float,
    *,
    kind: Optional[str] = None,
    source: str = "engine",
    cost: Optional[dict] = None,
    dedup=None,
) -> dict:
    """Ledger one compiled program.  ``key`` is the display key (the
    engine's program-ledger tuple); ``dedup``
    (default: the key itself) is the uniqueness key — the engine passes
    its ``(params-geometry, key)`` pair so two geometries compiling the
    same program key stay two entries, exactly like
    ``znicz_serve_compiles_total``.  Call ONLY on a true first compile;
    the caller owns that dedup (``PagedDecodeEngine._program``)."""
    kind = kind if kind is not None else (
        key[0] if isinstance(key, tuple) and key else str(key)
    )
    entry = {
        "key": str(key),
        "kind": str(kind),
        "source": source,
        "compile_s": round(float(compile_s), 6),
        "flops": (cost or {}).get("flops"),
        "bytes_accessed": (cost or {}).get("bytes_accessed"),
        "recorded_unix": time.time(),  # timestamp, not a delta
    }
    ledger_key = f"{source}:{dedup if dedup is not None else key}"
    with _LOCK:
        _PROGRAMS[ledger_key] = entry
    _m_compile_seconds().labels(kind=entry["kind"]).observe(
        float(compile_s)
    )
    if entry["flops"]:
        _m_cost_flops().labels(kind=entry["kind"]).inc(entry["flops"])
    if entry["bytes_accessed"]:
        _m_cost_bytes().labels(kind=entry["kind"]).inc(
            entry["bytes_accessed"]
        )
    return entry


def programs(source: Optional[str] = None) -> List[dict]:
    """The ledger entries in compile order (copies; filter by
    ``source`` — ``"engine"``)."""
    with _LOCK:
        return [
            dict(e) for e in _PROGRAMS.values()
            if source is None or e["source"] == source
        ]


def program_count(source: Optional[str] = None) -> int:
    with _LOCK:
        return sum(
            1 for e in _PROGRAMS.values()
            if source is None or e["source"] == source
        )


def compile_seconds_total() -> float:
    with _LOCK:
        return round(
            sum(e["compile_s"] for e in _PROGRAMS.values()), 6
        )


def ledger_snapshot() -> dict:
    """The ``/debug/programs`` body (also attached to bench records):
    the full entry list plus the headline counts the acceptance test
    pins against the engine ledger and ``znicz_serve_compiles_total``."""
    progs = programs()
    by_kind: Dict[str, int] = {}
    for e in progs:
        by_kind[e["kind"]] = by_kind.get(e["kind"], 0) + 1
    return {
        "programs": progs,
        "count": len(progs),
        "engine_count": sum(1 for e in progs if e["source"] == "engine"),
        "by_kind": by_kind,
        "compile_seconds_total": round(
            sum(e["compile_s"] for e in progs), 6
        ),
        "device_memory": device_memory(),
    }


# -- live device memory -----------------------------------------------------


def device_memory() -> List[dict]:
    """Per-device ``memory_stats()`` where the backend reports them
    (TPU/GPU; jax 0.4.37's CPU answers None — then the list carries
    the device with ``stats: null``).  Also refreshes the
    ``znicz_device_memory_bytes`` gauges.  Never raises; empty when
    jax itself is unavailable."""
    try:
        import jax

        devices = jax.devices()
    except Exception:
        logger.debug("jax devices unavailable", exc_info=True)
        return []
    out = []
    gauge = _m_device_memory()
    for d in devices:
        try:
            stats = d.memory_stats()
        except Exception:
            stats = None
        name = f"{getattr(d, 'platform', 'dev')}:{getattr(d, 'id', 0)}"
        out.append({"device": name, "stats": stats})
        if stats:
            for stat_key, gauge_kind in (
                ("bytes_in_use", "in_use"),
                ("peak_bytes_in_use", "peak"),
                ("bytes_limit", "limit"),
            ):
                v = stats.get(stat_key)
                if v is not None:
                    gauge.labels(kind=gauge_kind, device=name).set(
                        float(v)
                    )
    return out


# -- on-demand device capture -----------------------------------------------


def capture_profile(
    seconds: float, log_dir: Optional[str] = None
) -> dict:
    """One bounded ``jax.profiler`` device capture (``POST
    /debug/profile?seconds=N``): start a trace, sleep ``seconds``
    (clamped to ``PROFILE_MAX_SECONDS``), stop, return the capture
    directory.  The capture runs inside a ``debug/profile`` host span,
    so the device tracks line up with the host timeline (the tracer
    already wraps every span in ``jax.profiler.TraceAnnotation``).

    Raises ``ValueError`` on a non-finite duration (the HTTP layer
    answers 400), ``RuntimeError`` when a capture is already running
    (409) or the profiler is unavailable (503)."""
    s = float(seconds)
    if s != s or s in (float("inf"), float("-inf")):
        # NaN slides through min/max clamps (every comparison False)
        # and time.sleep(nan) raises — reject it at the door
        raise ValueError(f"want a finite duration; got {seconds!r}")
    s = min(max(s, 0.01), PROFILE_MAX_SECONDS)
    if not _PROFILE_LOCK.acquire(blocking=False):
        raise RuntimeError("a device profile capture is already running")
    try:
        try:
            import jax
        except Exception as exc:
            raise RuntimeError(f"jax unavailable: {exc}") from exc
        out_dir = log_dir or tempfile.mkdtemp(prefix="znicz-profile-")
        with span("debug/profile", seconds=s, log_dir=out_dir):
            try:
                jax.profiler.start_trace(out_dir)
            except Exception as exc:
                raise RuntimeError(
                    f"jax profiler unavailable: {exc}"
                ) from exc
            try:
                time.sleep(s)
            finally:
                jax.profiler.stop_trace()
        return {"log_dir": out_dir, "seconds": s}
    finally:
        _PROFILE_LOCK.release()
