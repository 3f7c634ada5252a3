"""Input-pipeline bottleneck attribution: where does a train step's wall go?

The streaming-rebuild rung (ROADMAP "the 100x training gap") cannot be
built blind: BENCH_r02-r04 measured 107-173 img/s streaming against
12,548 device-resident, and the only witness was one histogram
(``znicz_prefetch_wait_seconds``) that says "the consumer waited" but
not *why* — disk read, host decode, host->device transfer, or dispatch.
This module is the attribution layer on top of the per-stage
instrumentation:

* **Stage taxonomy** — the producer path (:mod:`znicz_tpu.loader
  .prefetch`) observes ``znicz_pipeline_stage_seconds{stage}`` for
  ``fetch`` (materializing one batch from the loader), ``host_transform``
  (decode/augment callables run in the producer thread) and ``enqueue``
  (blocked handing the batch over — depth exhaustion); the workflow's
  device-placement closure observes ``h2d`` through :class:`H2DProbe`
  (the ``device_put`` calls).  The four TILE the producer's loop: one
  :class:`StageClock` per producer thread makes each stage's end the
  next one's start, and ``znicz_pipeline_producer_seconds`` holds the
  wall of each whole iteration, so what no stage covers shows as
  ``producer_unattributed_frac`` instead of vanishing.  Three more
  labels stand beside the tile and are never added to it: ``crop_params``
  and ``crop`` (parts of ``fetch``, observed by ``ImageNetLoader.fill``)
  and ``h2d_landed`` (``device_put`` call to the arrays being ready on
  the device, taken by the probe's watcher thread; bytes over these
  seconds is the ``znicz_h2d_bytes_per_second`` gauge).
* **The serving thread's loop** — the same :class:`StageClock`, given
  the serving families (:func:`serving_loop_clock`:
  ``znicz_serve_loop_seconds{stage}`` and
  ``znicz_serve_loop_iteration_seconds``), tiles one turn of
  ``services/frontdoor.py`` and ``services/engine.py``
  (:data:`SERVE_LOOP_STAGES`; docs/SERVING.md).
* **:class:`PipelineAttribution`** — decomposes the per-step wall clock
  (``znicz_train_step_wall_seconds``) into fractions (compute /
  prefetch-wait / h2d / other) that sum to ~1.0, names the bottleneck
  with a confidence band, and suggests the next move.  Reads a live
  registry, a JSON snapshot, or a Prometheus exposition — the same
  three sources ``tools/znicz-doctor`` accepts.

Attribution math: the consumer's step wall is sliced into *compute*
(the ``dispatch/*`` phases of ``znicz_train_phase_seconds``),
*prefetch-wait* (``znicz_prefetch_wait_seconds``) and *other* (the
residual — untimed host work: python loop, stacking).  H2D is then
carved out of whichever slice it actually ran in: with the prefetch
thread on, the producer's ``h2d`` share of its busy time prorates the
wait slice (while the consumer waits, the producer is in one of its
stages, and the share is taken of the producer's own total less its
time blocked in ``enqueue``); with prefetching off the probe ran inline
on the consumer, so its seconds come out of the residual.  Either way
the four fractions are disjoint and sum to 1 (measurement jitter is
renormalized away).

Pure stdlib — importing this module must never pull in jax (the doctor
CLI runs on hosts with no accelerator stack).
"""

from __future__ import annotations

import contextlib
import logging
import math
import queue
import threading
import time
from collections import deque
from typing import Dict, Iterator, List, Optional, Tuple

from znicz_tpu.observability.registry import (
    MetricsRegistry,
    get_registry,
    parse_prometheus_text,
)
from znicz_tpu.observability.tracing import get_tracer
from znicz_tpu.utils import faults

logger = logging.getLogger(__name__)

# the producer/consumer stage taxonomy (docs/OBSERVABILITY.md
# "Training observability")
STAGE_FETCH = "fetch"
STAGE_TRANSFORM = "host_transform"
STAGE_H2D = "h2d"
STAGE_ENQUEUE = "enqueue"
# the stages that tile one producer iteration, in loop order
TILING_STAGES = (STAGE_FETCH, STAGE_TRANSFORM, STAGE_H2D, STAGE_ENQUEUE)
# parts of fetch (ImageNetLoader.fill) and the copy's landing time
# (H2DProbe's watcher): beside the tile, never added to it
STAGE_CROP_PARAMS = "crop_params"
STAGE_CROP = "crop"
STAGE_H2D_LANDED = "h2d_landed"

# where in the epoch a consumer's wait fell (znicz_prefetch_wait_seconds
# {at}): the first batch overlaps no step, the sentinel none either
WAIT_FIRST = "first"
WAIT_STEADY = "steady"
WAIT_END = "end"
# whether the producer thread was started for an epoch or went on into
# it from the one before (znicz_prefetch_epochs_total{start})
START_COLD = "cold"
START_CARRIED = "carried"

STEP_WALL_METRIC = "znicz_train_step_wall_seconds"
WAIT_METRIC = "znicz_prefetch_wait_seconds"
PHASE_METRIC = "znicz_train_phase_seconds"
STAGE_METRIC = "znicz_pipeline_stage_seconds"
PRODUCER_METRIC = "znicz_pipeline_producer_seconds"
H2D_BPS_METRIC = "znicz_h2d_bytes_per_second"
H2D_BYTES_METRIC = "znicz_h2d_bytes_total"
QUEUE_FULL_METRIC = "znicz_prefetch_queue_full_total"
PREFETCH_EPOCHS_METRIC = "znicz_prefetch_epochs_total"
CROP_IMAGES_METRIC = "znicz_loader_crop_images_total"
STAGING_BUFFERS_METRIC = "znicz_loader_staging_buffers_total"

# anomaly surfaces the doctor reads from the same exposition
ANOMALY_ACTIVE_METRIC = "znicz_train_anomaly_active"
ANOMALY_TOTAL_METRIC = "znicz_train_anomalies_total"
LAST_LOSS_METRIC = "znicz_train_last_loss"
LAST_GRAD_METRIC = "znicz_train_last_grad_norm"

# self-healing surfaces (docs/TRAINING.md): the training tier's
# detect->recover loop.  Defined HERE (stdlib-pure) so both the
# producers (workflow/recovery.py, launcher.py, loader/base.py) and the
# doctor's readout speak one name per signal.
ROLLBACKS_METRIC = "znicz_train_rollbacks_total"
ROLLBACK_GIVE_UP_METRIC = "znicz_train_rollback_give_up"
RESTARTS_METRIC = "znicz_train_restarts_total"
RESTART_BUDGET_METRIC = "znicz_train_restart_budget"
LOADER_RETRIES_METRIC = "znicz_loader_retries_total"
LOADER_SKIPPED_METRIC = "znicz_loader_skipped_batches_total"
SNAPSHOT_FAILURES_METRIC = "znicz_train_snapshot_failures_total"

# the serving thread's loop (services/frontdoor.py, services/engine.py;
# docs/SERVING.md "Where a turn of the serving thread goes"): leaves that
# tile one turn, label value = span name
SERVE_LOOP_STAGE_METRIC = "znicz_serve_loop_seconds"
SERVE_LOOP_ITERATION_METRIC = "znicz_serve_loop_iteration_seconds"
_CHUNK_PARTS = ("grow", "prepare", "dispatch", "wait", "fetch", "emit")
SERVE_LOOP_STAGES = (
    "frontdoor/control", "frontdoor/pump", "serve/schedule",
    "serve/prefill/host", "serve/prefill/wait",
    *(f"serve/decode/{part}" for part in _CHUNK_PARTS),
    "serve/verify/draft",
    *(f"serve/verify/{part}" for part in _CHUNK_PARTS),
    "frontdoor/stream", "frontdoor/housekeeping",
)

# the families a warm-up window reset clears (bench/tests exclude the
# first epoch's compile stall from the attribution they report)
WINDOW_METRICS = (
    STEP_WALL_METRIC,
    WAIT_METRIC,
    PHASE_METRIC,
    STAGE_METRIC,
    PRODUCER_METRIC,
    H2D_BYTES_METRIC,
    QUEUE_FULL_METRIC,
    PREFETCH_EPOCHS_METRIC,
)


def stage_seconds(registry: Optional[MetricsRegistry] = None):
    """The shared per-stage histogram family (get-or-create)."""
    reg = registry if registry is not None else get_registry()
    return reg.histogram(
        STAGE_METRIC,
        "input-pipeline per-stage wall seconds (fetch / host_transform / "
        "h2d / enqueue tile the producer's loop; crop_params and crop are "
        "parts of fetch; h2d_landed is device_put call to arrays ready)",
        ("stage",),
    )


def producer_seconds(registry: Optional[MetricsRegistry] = None):
    """Wall of each whole producer iteration (get-or-create): the total
    the tiling stages are held to."""
    reg = registry if registry is not None else get_registry()
    return reg.histogram(
        PRODUCER_METRIC,
        "wall seconds of one whole prefetch-producer iteration "
        "(fetch -> transform or h2d -> enqueue)",
    )


def wait_seconds(registry: Optional[MetricsRegistry] = None):
    """Consumer-side wait for the next minibatch, labelled by where in
    the epoch it fell (get-or-create)."""
    reg = registry if registry is not None else get_registry()
    return reg.histogram(
        WAIT_METRIC,
        "seconds the consumer blocked waiting for the next minibatch "
        "(at = first batch of an epoch / steady / end-of-epoch sentinel)",
        ("at",),
    )


def prefetch_epochs(registry: Optional[MetricsRegistry] = None):
    """Epochs the consumer began behind a prefetch producer, by how the
    producer came to them (get-or-create)."""
    reg = registry if registry is not None else get_registry()
    return reg.counter(
        PREFETCH_EPOCHS_METRIC,
        "epochs whose first batch the consumer took from a prefetch "
        "producer (start = cold: the thread was started for the epoch / "
        "carried: it went on from the previous epoch)",
        ("start",),
    )


def crop_images(registry: Optional[MetricsRegistry] = None):
    """Images cropped on the host, by the path the crop took
    (get-or-create)."""
    reg = registry if registry is not None else get_registry()
    return reg.counter(
        CROP_IMAGES_METRIC,
        "images ImageNetLoader.fill cropped on the host (path = copy: "
        "unflipped, a memcpy a row / flip_wide: flipped, sixteen bytes a "
        "turn / flip_pixel: flipped, pixel by pixel / numpy: the native "
        "library was not used)",
        ("path",),
    )


def staging_buffers(registry: Optional[MetricsRegistry] = None):
    """Batches of host crops by where their buffer came from
    (get-or-create)."""
    reg = registry if registry is not None else get_registry()
    return reg.counter(
        STAGING_BUFFERS_METRIC,
        "batches ImageNetLoader.fill cropped on the host (source = "
        "recycled: into a buffer of an earlier batch that nothing refers "
        "to any more / fresh: into a new allocation, which the kernel "
        "zero-fills page by page under the crop's first writes)",
        ("source",),
    )


def step_wall_seconds(registry: Optional[MetricsRegistry] = None):
    """Consumer-side per-train-step wall histogram (get-or-create)."""
    reg = registry if registry is not None else get_registry()
    return reg.histogram(
        STEP_WALL_METRIC,
        "wall seconds per training step as seen by the consumer loop "
        "(prefetch wait + dispatch + host bookkeeping)",
    )


def serving_loop_clock() -> "StageClock":
    """The serving thread's stage clock: one turn of
    ``ServingFrontDoor._tick`` (or of ``PagedDecodeEngine.tick`` under
    ``run()``) is an iteration, :data:`SERVE_LOOP_STAGES` its stages."""
    reg = get_registry()
    return StageClock(
        reg.histogram(
            SERVE_LOOP_STAGE_METRIC,
            "serving-thread wall seconds by stage: the stages tile one "
            "turn of the front door and the engine (frontdoor/* around "
            "serve/*; */wait is where the thread blocks on the device)",
            ("stage",),
        ),
        reg.histogram(
            SERVE_LOOP_ITERATION_METRIC,
            "wall seconds of one whole turn of the serving thread that "
            "had work (the total the stages are held to)",
        ),
    )


def reset_window(registry: Optional[MetricsRegistry] = None) -> None:
    """Zero the attribution-relevant series (warm-up exclusion: call
    after the compile epoch so the reported window is steady-state).
    Families that don't exist yet are simply skipped."""
    reg = registry if registry is not None else get_registry()
    fams = reg.metrics()
    for name in WINDOW_METRICS:
        m = fams.get(name)
        if m is not None:
            m.reset()


_clock_local = threading.local()


class StageClock:
    """Contiguous stage timing on one thread's loop.

    ``stages`` is the histogram family (label ``stage``) the laps go to
    and ``iterations`` the family that holds each whole turn of the loop:
    the prefetch producer passes :func:`stage_seconds` /
    :func:`producer_seconds`, the serving thread
    :func:`serving_loop_clock`'s.

    ``lap(stage)`` observes the time since the previous lap (or skip)
    into the stage histogram and starts the next stage at that same
    clock read, so the stages of one iteration leave no hole between
    them; ``skip()`` moves the mark without observing — that time
    belongs to no stage and shows as unattributed.  ``close_iteration``
    observes mark-to-mark into the iteration family: the sum of the
    laps equals the sum of the iterations exactly unless something
    skipped.  ``stage(name)`` is a lap AND a tracer span of the same
    name: the span opens where the caller says, the lap runs from the
    previous mark to the span's end.

    The clock runs from ``start()`` to ``stop()`` (a ``with`` block does
    both, and makes the clock this thread's current one, which is how
    :class:`H2DProbe` joins the tile from inside a transform callable);
    while it does not run a lap observes nothing, so a loop that turns
    without work (the front door's idle tick) books neither stages nor
    an iteration."""

    def __init__(self, stages, iterations):
        self._stages = stages
        self._iterations = iterations
        self._tracer = get_tracer()
        self.running = False
        self.mark = self._iteration_start = 0.0

    def start(self) -> None:
        self.mark = self._iteration_start = time.perf_counter()
        self.running = True

    def stop(self) -> None:
        self.running = False

    def __enter__(self) -> "StageClock":
        _clock_local.clock = self
        self.start()
        return self

    def __exit__(self, *exc) -> None:
        self.stop()
        _clock_local.clock = None

    def lap(self, stage: str, family=None) -> float:
        """Close ``stage`` at this clock read (``family``: the stage
        histogram of an instrument that keeps its own registry)."""
        if not self.running:
            return 0.0
        now = time.perf_counter()
        seconds, self.mark = now - self.mark, now
        (family or self._stages).labels(stage=stage).observe(seconds)
        return seconds

    @contextlib.contextmanager
    def stage(self, name: str) -> Iterator[None]:
        """Span ``name`` around the block, then ``lap(name)``: on the
        device trace's clock the span names what the thread did, in the
        registry the lap also holds the untimed steps since the stage
        before."""
        try:
            with self._tracer.span(name):
                yield
        finally:
            self.lap(name)

    def skip(self) -> None:
        self.mark = time.perf_counter()

    def close_iteration(self) -> None:
        if not self.running:
            return
        self._iterations.observe(self.mark - self._iteration_start)
        self._iteration_start = self.mark


class _Transfer:
    """What ``H2DProbe.measure`` yields: ``watch(*arrays)`` names the
    device arrays whose landing the probe's watcher should time."""

    __slots__ = ("arrays",)

    def __init__(self):
        self.arrays: tuple = ()

    def watch(self, *arrays) -> None:
        self.arrays = tuple(
            a for a in arrays if hasattr(a, "block_until_ready")
        )


class H2DProbe:
    """Host->device transfer probe: bytes moved, call time, landing time.

    ``with probe.measure(nbytes) as put:`` around the device placement
    calls observes the ``h2d`` stage (the *call* wall: ``device_put``
    returns before the copy lands) and counts ``znicz_h2d_bytes_total``.
    ``put.watch(x, y, ...)`` hands the placed arrays to a watcher thread
    that waits for them (``block_until_ready``) and observes
    ``h2d_landed``: from the start of the measured region to the arrays
    being ready on the device.  The caller never waits for the watcher,
    so neither the batch's hand-off nor the producer's next fetch is
    delayed.  The live ``znicz_h2d_bytes_per_second`` gauge divides a
    rolling window of bytes by landed seconds (by call seconds where
    nothing was watched).

    Under a :class:`StageClock` (the prefetch producer) the measured
    region starts where the previous stage ended.  The ``loader.h2d``
    fault point fires inside the measured region, so an injected delay
    reads as a slow link to the attribution — the CI fixture for the
    h2d-bound verdict.
    """

    def __init__(
        self,
        registry: Optional[MetricsRegistry] = None,
        *,
        window: int = 64,
    ):
        reg = registry if registry is not None else get_registry()
        self._hist = stage_seconds(reg)
        self._bytes = reg.counter(
            H2D_BYTES_METRIC,
            "bytes transferred host->device by the training loader path",
        )
        self._bps = reg.gauge(
            H2D_BPS_METRIC,
            "live host->device transfer rate over the last ~window of "
            "training batches (bytes over landed seconds)",
        )
        self._recent: deque = deque(maxlen=max(int(window), 1))
        self._lock = threading.Lock()
        self._landing: "queue.Queue" = queue.Queue()
        self._watcher: Optional[threading.Thread] = None

    @contextlib.contextmanager
    def measure(self, nbytes: int) -> Iterator[_Transfer]:
        clock = getattr(_clock_local, "clock", None)
        t0 = clock.mark if clock is not None else time.perf_counter()
        transfer = _Transfer()
        try:
            with get_tracer().span("loader/h2d"):
                faults.fire("loader.h2d")
                yield transfer
        finally:
            # bookkeeping first, the clock read late: under a stage
            # clock whatever follows the lap falls to no stage
            if nbytes > 0:
                self._bytes.inc(float(nbytes))
            if transfer.arrays:
                self._ensure_watcher()
            if clock is not None:
                seconds = clock.lap(STAGE_H2D, self._hist)
            else:
                seconds = time.perf_counter() - t0
                self._hist.labels(stage=STAGE_H2D).observe(seconds)
            if transfer.arrays:
                # handed over after the lap, so the watcher's clock read
                # follows it: a batch's landing time is never under its
                # call time
                self._landing.put((t0, nbytes, transfer.arrays))
            else:
                self._rate(nbytes, seconds)

    def observe(self, nbytes: int, seconds: float) -> None:
        """One transfer timed by the caller (call time only)."""
        self._hist.labels(stage=STAGE_H2D).observe(seconds)
        if nbytes > 0:
            self._bytes.inc(float(nbytes))
        self._rate(nbytes, seconds)

    def _rate(self, nbytes: int, seconds: float) -> None:
        with self._lock:
            self._recent.append((float(nbytes), float(seconds)))
            total_b = sum(b for b, _ in self._recent)
            total_s = sum(s for _, s in self._recent)
        if total_s > 0:
            self._bps.set(total_b / total_s)

    # -- the landing watcher -----------------------------------------------

    def _ensure_watcher(self) -> None:
        if self._watcher is not None and self._watcher.is_alive():
            return
        with self._lock:
            if self._watcher is None or not self._watcher.is_alive():
                # outside its try the loop only blocks in Queue.get(),
                # which does not raise; a dead watcher is restarted here
                self._watcher = threading.Thread(  # znicz-check: disable=ZNC013
                    target=self._watch_landings,
                    name="znicz-h2d-landing",
                    daemon=True,
                )
                self._watcher.start()

    def _watch_landings(self) -> None:
        while True:
            t0, nbytes, arrays = self._landing.get()
            try:
                with get_tracer().span("loader/h2d_landed"):
                    for a in arrays:
                        # znicz-check: disable=ZNC007 -- waiting for the
                        # copy is this thread's whole job; no dispatch
                        # runs here
                        a.block_until_ready()  # znicz-check: disable=ZNC007
                seconds = time.perf_counter() - t0
                self._hist.labels(stage=STAGE_H2D_LANDED).observe(seconds)
                self._rate(nbytes, seconds)
            except Exception:
                # a deleted or donated buffer: the batch itself is the
                # consumer's business, the instrument only loses a sample
                logger.debug("h2d landing watch lost a batch", exc_info=True)
            finally:
                del arrays
                self._landing.task_done()

    def drain(self, timeout: float = 10.0) -> bool:
        """Wait until the watcher has seen every batch handed to it so
        far; False if it has not within ``timeout`` seconds (tests and
        end-of-run readers; the hot path never calls it)."""
        deadline = time.perf_counter() + timeout
        while self._landing.unfinished_tasks:
            if time.perf_counter() > deadline:
                return False
            time.sleep(0.001)
        return True


# -- attribution ------------------------------------------------------------

_SUGGESTIONS = {
    "input": (
        "raise prefetch depth, shard loaders across processes, or move "
        "decode/augment on-device (the streaming-rebuild rung)"
    ),
    "h2d": (
        "overlap H2D with compute (double-buffered device prefetch), "
        "batch transfers, or ship compact dtypes (u8 + on-device "
        "normalize)"
    ),
    "compute": (
        "input pipeline keeps up — optimize the step itself or scale "
        "devices"
    ),
    "other": (
        "untimed host work dominates (python loop, stacking, metric "
        "sync) — record a tracer window to see where"
    ),
}

# above this share of the producer's loop in no stage, a verdict is "low"
UNATTRIBUTED_LOW_CONFIDENCE = 0.1

_VERDICTS = {
    "input": "input-bound",
    "h2d": "h2d-bound",
    "compute": "compute-bound",
    "other": "unattributed",
}


class PipelineAttribution:
    """Step-wall decomposition over one metrics capture.

    Construct from a live registry (:meth:`from_registry`), a registry
    JSON snapshot (:meth:`from_snapshot` — the ``status.json`` /
    bench-record shape, self-describing non-metric entries like
    ``{"type": "slo"}`` are skipped), or a Prometheus text exposition
    (:meth:`from_prometheus` — a ``metrics.prom`` file or an
    aggregator's merged ``/metrics``; pass ``instance=`` to scope a
    fleet exposition to one process).  :meth:`attribution` returns the
    self-describing ``{"type": "pipeline", ...}`` record the bench
    attaches and ``znicz-doctor`` prints.
    """

    def __init__(self, samples: List[Tuple[str, Dict[str, str], float]]):
        # prometheus-shaped flat samples: histograms appear as
        # <name>_sum / <name>_count / <name>_bucket rows
        self._samples = samples

    # -- constructors ------------------------------------------------------

    @classmethod
    def from_registry(
        cls, registry: Optional[MetricsRegistry] = None
    ) -> "PipelineAttribution":
        reg = registry if registry is not None else get_registry()
        return cls.from_snapshot(reg.snapshot())

    @classmethod
    def from_snapshot(cls, snap: dict) -> "PipelineAttribution":
        samples: List[Tuple[str, Dict[str, str], float]] = []
        for name, fam in snap.items():
            if not isinstance(fam, dict):
                continue
            kind = fam.get("type")
            series = fam.get("series")
            # self-describing riders ({"type": "slo"/"programs"/
            # "pipeline"}) are not metric families
            if kind not in ("counter", "gauge", "histogram") or not (
                isinstance(series, list)
            ):
                continue
            for s in series:
                labels = dict(s.get("labels") or {})
                if kind == "histogram":
                    samples.append(
                        (f"{name}_sum", labels, float(s.get("sum", 0.0)))
                    )
                    samples.append(
                        (
                            f"{name}_count",
                            labels,
                            float(s.get("count", 0.0)),
                        )
                    )
                else:
                    samples.append(
                        (name, labels, float(s.get("value", 0.0)))
                    )
        return cls(samples)

    @classmethod
    def from_prometheus(
        cls, text: str, *, instance: Optional[str] = None
    ) -> "PipelineAttribution":
        """Raises ``ValueError`` on a malformed exposition (the doctor
        maps it to the usage exit)."""
        parsed = parse_prometheus_text(text)
        samples = [
            (name, labels, value)
            for name, labels, value in parsed["samples"]
            if instance is None or labels.get("instance") == instance
        ]
        return cls(samples)

    # -- sample queries ----------------------------------------------------

    def _sum(self, name: str, **want: str) -> float:
        total = 0.0
        for sname, labels, value in self._samples:
            if sname != name:
                continue
            if any(labels.get(k) != v for k, v in want.items()):
                continue
            total += value
        return total

    def _sum_label_prefix(self, name: str, label: str, prefix: str) -> float:
        total = 0.0
        for sname, labels, value in self._samples:
            if sname == name and str(labels.get(label, "")).startswith(
                prefix
            ):
                total += value
        return total

    def _gauge_max(self, name: str) -> Optional[float]:
        vals = [
            value for sname, _, value in self._samples if sname == name
        ]
        return max(vals) if vals else None

    # -- the verdict -------------------------------------------------------

    def attribution(self) -> dict:
        wall = self._sum(f"{STEP_WALL_METRIC}_sum")
        steps = self._sum(f"{STEP_WALL_METRIC}_count")
        stages = {
            s: self._sum(f"{STAGE_METRIC}_sum", stage=s)
            for s in TILING_STAGES
            + (STAGE_CROP_PARAMS, STAGE_CROP, STAGE_H2D_LANDED)
        }
        tiled = sum(stages[s] for s in TILING_STAGES)
        # captures from before the producer total existed (or synthetic
        # ones without it) fall back to the stages' own sum: no hole
        # can be seen there, and none is claimed
        producer = self._sum(f"{PRODUCER_METRIC}_sum") or tiled
        unattributed = max(1.0 - tiled / producer, 0.0) if producer else 0.0
        out: dict = {
            "type": "pipeline",
            "steps": int(steps),
            "wall_seconds": round(wall, 6),
            "stages": {k: round(v, 6) for k, v in stages.items()},
            "producer_seconds": round(producer, 6),
            "producer_unattributed_frac": round(unattributed, 4),
            "waits": {
                at: round(self._sum(f"{WAIT_METRIC}_sum", at=at), 6)
                for at in (WAIT_FIRST, WAIT_STEADY, WAIT_END)
            },
            "queue_full_stalls": int(self._sum(QUEUE_FULL_METRIC)),
            "h2d_bytes_per_second": self._bandwidth(stages),
        }
        if steps <= 0 or wall <= 0:
            out.update(
                {
                    "fractions": {},
                    "bottleneck": None,
                    "verdict": "no-data",
                    "confidence": "none",
                    "margin": 0.0,
                    "input_bound_frac": 0.0,
                    "suggestion": (
                        "no znicz_train_step_wall_seconds samples in this "
                        "capture — run a stepwise training window first"
                    ),
                }
            )
            return out

        wait = min(self._sum(f"{WAIT_METRIC}_sum"), wall)
        wait_count = self._sum(f"{WAIT_METRIC}_count")
        compute = min(
            self._sum_label_prefix(f"{PHASE_METRIC}_sum", "phase", "dispatch/"),
            wall,
        )
        h2d_raw = stages[STAGE_H2D]
        if wait_count > 0:
            # prefetch thread on: while the consumer waits, the producer
            # is somewhere in its loop — prorate the wait slice by the
            # h2d share of the producer's own total less its time
            # blocked in enqueue (a hole between stages stays in the
            # denominator, so it cannot inflate either share)
            busy = producer - stages[STAGE_ENQUEUE]
            h2d_frac = (
                (wait / wall) * (h2d_raw / busy) if busy > 0 else 0.0
            )
            wait_frac = max(wait / wall - h2d_frac, 0.0)
        else:
            # no prefetch thread: the probe ran inline on the consumer,
            # its wall sits in the residual outside the dispatch phases
            h2d_frac = min(h2d_raw, max(wall - compute, 0.0)) / wall
            wait_frac = 0.0
        compute_frac = compute / wall
        measured = compute_frac + wait_frac + h2d_frac
        if measured > 1.0:
            # phase/wait timers overlap the wall by jitter: renormalize
            # so the reported fractions stay a partition of 1
            compute_frac /= measured
            wait_frac /= measured
            h2d_frac /= measured
            measured = 1.0
        other_frac = max(1.0 - measured, 0.0)
        fractions = {
            "compute": round(compute_frac, 4),
            "prefetch_wait": round(wait_frac, 4),
            "h2d": round(h2d_frac, 4),
            "other": round(other_frac, 4),
        }
        by_bottleneck = {
            "compute": compute_frac,
            "input": wait_frac,
            "h2d": h2d_frac,
            "other": other_frac,
        }
        ranked = sorted(
            by_bottleneck.items(), key=lambda kv: -kv[1]
        )
        top, top_frac = ranked[0]
        margin = top_frac - ranked[1][1]
        band = min(0.5, 1.0 / math.sqrt(steps))
        if steps >= 20 and margin >= 2 * band:
            confidence = "high"
        elif steps >= 8 and margin >= band:
            confidence = "medium"
        else:
            confidence = "low"
        if unattributed > UNATTRIBUTED_LOW_CONFIDENCE:
            # the producer spent time no stage covers: the shares above
            # divide a total they do not explain
            confidence = "low"
        out.update(
            {
                "fractions": fractions,
                "fractions_sum": round(sum(fractions.values()), 4),
                "bottleneck": top,
                "verdict": _VERDICTS[top],
                "confidence": confidence,
                "margin": round(margin, 4),
                "confidence_band": [
                    round(max(top_frac - band, 0.0), 4),
                    round(min(top_frac + band, 1.0), 4),
                ],
                "input_bound_frac": round(wait_frac + h2d_frac, 4),
                "suggestion": _SUGGESTIONS[top],
            }
        )
        return out

    def _bandwidth(self, stages: Dict[str, float]) -> Optional[float]:
        """Window-consistent first: bytes / landed seconds (call
        seconds in a capture that has no landing time) — all zeroed
        together by :func:`reset_window`, so the headline never blends
        the compile epoch back in.  The live gauge (a rolling probe
        window reset_window cannot reach) is only the fallback for
        captures without the counter."""
        total = self._sum(H2D_BYTES_METRIC)
        seconds = stages.get(STAGE_H2D_LANDED) or stages.get(STAGE_H2D, 0.0)
        if total > 0 and seconds > 0:
            return round(total / seconds, 1)
        live = self._gauge_max(H2D_BPS_METRIC)
        if live:
            return round(live, 1)
        return None

    def anomaly_summary(self) -> dict:
        """The anomaly view of the same capture: active flag, per-type
        counts and the last loss/grad-norm gauges — what the doctor's
        exit-1 gate reads (the full ring lives in ``status.json``)."""
        active = self._gauge_max(ANOMALY_ACTIVE_METRIC)
        counts: Dict[str, float] = {}
        for name, labels, value in self._samples:
            if name == ANOMALY_TOTAL_METRIC and value > 0:
                key = labels.get("type", "unknown")
                counts[key] = counts.get(key, 0.0) + value
        return {
            "active": bool(active),
            "counts": {k: int(v) for k, v in sorted(counts.items())},
            "total": int(sum(counts.values())),
            "last_loss": self._gauge_max(LAST_LOSS_METRIC),
            "last_grad_norm": self._gauge_max(LAST_GRAD_METRIC),
        }

    def recovery_summary(self) -> dict:
        """The self-healing view of the same capture: rollback /
        restart / loader-retry counters plus the give-up signals
        ``znicz-doctor`` gates on.  ``looping`` is True when the run
        has burned its whole restart budget (the supervisor is about
        to — or already did — give up) or a rollback gave up: both are
        "this run is not healing itself" incidents, the doctor's
        exit-1 condition."""
        rollbacks: Dict[str, int] = {}
        for name, labels, value in self._samples:
            if name == ROLLBACKS_METRIC and value > 0:
                key = labels.get("reason", "unknown")
                rollbacks[key] = rollbacks.get(key, 0) + int(value)
        restarts = int(self._sum(RESTARTS_METRIC))
        budget = self._gauge_max(RESTART_BUDGET_METRIC)
        give_up = bool(self._gauge_max(ROLLBACK_GIVE_UP_METRIC))
        looping = give_up or (
            budget is not None and budget > 0 and restarts >= budget
        )
        return {
            "rollbacks": dict(sorted(rollbacks.items())),
            "rollbacks_total": sum(rollbacks.values()),
            "rollback_give_up": give_up,
            "restarts": restarts,
            "restart_budget": int(budget) if budget is not None else None,
            "loader_retries": int(self._sum(LOADER_RETRIES_METRIC)),
            "loader_skipped_batches": int(
                self._sum(LOADER_SKIPPED_METRIC)
            ),
            "snapshot_failures": int(
                self._sum(SNAPSHOT_FAILURES_METRIC)
            ),
            "looping": looping,
        }
