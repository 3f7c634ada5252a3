"""Background-thread minibatch prefetching.

The reference hid loader latency behind its event-driven thread pool (the
loader unit ran concurrently with device units, SURVEY.md 1 L4); the rebuilt
hot loop is a single host thread, so decode/gather work (image files, u8
conversion) would serialize with device dispatch.  ``prefetch`` runs the
loader's generator in a worker thread with a small bounded queue — identical
yield order and PRNG draw sequence, overlapped with compute.

The producer is stage-instrumented (docs/OBSERVABILITY.md "Training
observability"): each item's **fetch** (materializing one batch from the
upstream iterable), optional **host transform** (a ``transform`` callable
run on the producer thread — decode/augment, or the workflow's device
placement) and **enqueue** (blocked handing the batch over) observe into
``znicz_pipeline_stage_seconds{stage}`` and emit matching tracer spans, so
"producer slow" (long fetch/transform) and "producer starved" (long
enqueue — the consumer is the bottleneck and the queue stayed full,
counted by ``znicz_prefetch_queue_full_total``) are distinguishable in
one capture.  The stages TILE the loop: one
:class:`~znicz_tpu.observability.pipeline.StageClock` makes each stage's
end the next one's start, and the wall of each whole iteration goes to
``znicz_pipeline_producer_seconds``, so time that falls between stages
is seen.  The ``loader.fetch`` fault point fires inside the timed fetch,
making a slow producer a deterministic CI fixture.

The consumer's wait (``znicz_prefetch_wait_seconds``) is labelled by
where in the epoch it fell: ``at="first"`` (the first batch of an epoch),
``"steady"``, and ``"end"`` (the wait for the end of the epoch: an
:class:`EpochEnd` marker, or the sentinel of an iterable that ends).

**The producer outlives the epoch.**  An iterable that goes on past an
epoch's end says so by yielding an :class:`EpochEnd` between epochs.
The producer hands the marker over like a batch (no transform) and goes
straight on with the next epoch, so that epoch's first ``depth`` batches
are fetched, placed and landed while the consumer still runs the last
steps of this one and waits for their metrics; the queue's depth bounds
the run-ahead exactly as it does inside an epoch.  The consumer labels
its wait for the marker ``end`` and the pull after it ``first`` again:
with a carried producer ``first`` reads what is LEFT of the edge (near
zero when the batch was already queued), where a producer started for
the epoch pays a whole fetch and placement there.
``znicz_prefetch_epochs_total{start}`` counts, as the consumer takes an
epoch's first batch, whether the producer thread was started for that
epoch (``cold``) or went on into it from the one before (``carried``).

:class:`CarriedEpochs` is that arrangement for a :class:`Loader`: one
producer over ``loader.epoch()`` after ``loader.epoch()``, each marker
carrying the loader's state as it stood at the boundary (the producer
is the only thread that can name that moment: by the time the consumer
reads the marker the loader is already into the next epoch), and
``park()`` to stop the thread and put the loader back to the last
boundary the consumer reached.
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Any, Callable, Iterable, Iterator, Optional, TypeVar

from znicz_tpu import observability
from znicz_tpu.core import prng
from znicz_tpu.observability import pipeline as _pipeline
from znicz_tpu.utils import faults

T = TypeVar("T")

_SENTINEL = object()
THREAD_NAME = "znicz-prefetch"


class EpochEnd:
    """What an iterable that spans epochs yields between two of them.
    ``state`` is whatever the iterable wants the consumer to know about
    the boundary (:class:`CarriedEpochs` puts the loader's state there)."""

    __slots__ = ("state",)

    def __init__(self, state: Any = None):
        self.state = state


class PrefetchProducerError(RuntimeError):
    """The prefetch producer thread died without delivering its
    end-of-epoch sentinel (or the error that killed it) — the typed,
    consumer-visible form of a dead producer.  Ordinary producer
    exceptions re-raise AS THEMSELVES at the consumer's next pull; this
    only fires when the thread is gone and nothing explains why (e.g.
    it never started), turning what used to be an unbounded ``q.get()``
    hang into a diagnosis (the ZNC013 "a thread death must be a typed
    event" contract)."""


def prefetch(
    iterable: Iterable[T],
    depth: int = 2,
    *,
    transform: Optional[Callable[[T], T]] = None,
    transform_stage: Optional[str] = _pipeline.STAGE_TRANSFORM,
) -> Iterator[T]:
    """Yield from ``iterable``, produced ``depth`` items ahead in a thread.

    ``transform`` (optional) is applied to each item ON the producer
    thread — host decode/augment work, or the workflow's device-placement
    closure — timed as the ``transform_stage`` pipeline stage (pass
    ``transform_stage=None`` when the callable owns its own
    instrumentation, e.g. an :class:`~znicz_tpu.observability.H2DProbe`,
    which joins the producer's stage clock; what such a callable leaves
    unobserved belongs to no stage and shows as unattributed).

    An :class:`EpochEnd` from the iterable passes through untransformed
    and restarts the consumer's ``first`` / ``steady`` / ``end`` labels;
    the producer does not pause at it (module docstring).

    Exceptions in the producer (fetch or transform) re-raise at the
    consumer's next pull.  If the consumer abandons the iterator
    (exception mid-epoch, interrupt), closing the generator signals the
    worker to stop — no thread or queued batches leak.
    """
    q: queue.Queue = queue.Queue(maxsize=max(depth, 1))
    stop = threading.Event()
    error: list = []

    # per-stage producer telemetry: each span is on the LOADER's own
    # thread track in Perfetto, so producer stalls line up against the
    # consumer's znicz_prefetch_wait_seconds histogram and the
    # train/serve spans they starve.  One TraceAnnotation and one
    # histogram observe per stage per item.
    queue_full = observability.counter(
        _pipeline.QUEUE_FULL_METRIC,
        "items whose producer-side enqueue found the prefetch queue "
        "full at least once (depth exhaustion: the consumer, not the "
        "producer, is behind)",
    )

    def worker():
        tracer = observability.get_tracer()
        try:
            it = iter(iterable)
            # one iteration is fetch -> transform or h2d -> enqueue; the
            # clock makes each stage end where the next starts
            with _pipeline.StageClock(
                _pipeline.stage_seconds(), _pipeline.producer_seconds()
            ) as clock:
                while True:
                    with tracer.span("loader/fetch"):
                        # the fault fires INSIDE the timed window, so an
                        # injected delay reads as a slow producer to the
                        # attribution (the input-bound CI fixture)
                        faults.fire("loader.fetch")
                        item = next(it, _SENTINEL)
                    clock.lap(_pipeline.STAGE_FETCH)
                    if item is _SENTINEL or stop.is_set():
                        # a consumer that went away during the fetch is
                        # owed neither the placement nor the hand-over
                        clock.close_iteration()
                        break
                    # an EpochEnd is handed over as it is; its wait in
                    # the queue is an enqueue like any batch's, so the
                    # stages go on tiling the loop across the boundary
                    if transform is not None and not isinstance(
                        item, EpochEnd
                    ):
                        if transform_stage is None:
                            # the callable laps its own stage on this
                            # clock (an H2DProbe does, and its hand-over
                            # tail counts as enqueue); one that observes
                            # nothing spent time that is in no stage,
                            # and that must not leak into enqueue
                            mark = clock.mark
                            item = transform(item)
                            if clock.mark == mark:
                                clock.skip()
                        else:
                            with tracer.span(f"loader/{transform_stage}"):
                                item = transform(item)
                            clock.lap(transform_stage)
                    # bounded put that gives up when the consumer went away
                    try:
                        # non-blocking first attempt: ANY fullness counts
                        # as a depth-exhaustion stall, even one shorter
                        # than the polling timeout below
                        q.put_nowait(item)
                    except queue.Full:  # znicz-check: disable=ZNC008
                        queue_full.inc()
                        while not stop.is_set():
                            try:
                                q.put(item, timeout=0.1)
                                break
                            # polling control flow, not a swallowed failure
                            except queue.Full:  # znicz-check: disable=ZNC008
                                continue
                    clock.lap(_pipeline.STAGE_ENQUEUE)
                    clock.close_iteration()
                    if stop.is_set():
                        return
        except BaseException as e:  # noqa: BLE001 — must cross threads
            error.append(e)
        finally:
            # deliver the sentinel with the same give-up-on-stop loop as
            # items: a fixed timeout would lose it when the consumer stalls
            # longer (e.g. first-step XLA compile) and deadlock the epoch
            while not stop.is_set():
                try:
                    q.put(_SENTINEL, timeout=0.1)
                    break
                # polling control flow, not a swallowed failure
                except queue.Full:  # znicz-check: disable=ZNC008
                    continue

    # how long the training loop blocked waiting on the loader: the
    # "is the input pipeline the bottleneck" histogram — near-zero waits
    # mean the device is the limit; long waits mean the loader is
    wait = _pipeline.wait_seconds()
    at = _pipeline.WAIT_FIRST
    epochs = _pipeline.prefetch_epochs()
    start = _pipeline.START_COLD
    t = threading.Thread(target=worker, daemon=True, name=THREAD_NAME)
    t.start()
    try:
        while True:
            t0 = time.perf_counter()
            # bounded get with a liveness check: a producer thread that
            # died without its sentinel (hard kill, never started) must
            # become a typed error, not an unbounded q.get() hang
            while True:
                try:
                    item = q.get(timeout=0.5)
                    break
                except queue.Empty:  # znicz-check: disable=ZNC008
                    if not t.is_alive() and q.empty():
                        if error:
                            raise error[0]
                        raise PrefetchProducerError(
                            "prefetch producer thread died without "
                            "delivering a sentinel or an error"
                        )
            waited = time.perf_counter() - t0
            if item is _SENTINEL:
                wait.labels(at=_pipeline.WAIT_END).observe(waited)
                if error:
                    raise error[0]
                return
            if isinstance(item, EpochEnd):
                wait.labels(at=_pipeline.WAIT_END).observe(waited)
                at, start = _pipeline.WAIT_FIRST, _pipeline.START_CARRIED
            else:
                if at == _pipeline.WAIT_FIRST:
                    epochs.labels(start=start).inc()
                wait.labels(at=at).observe(waited)
                at = _pipeline.WAIT_STEADY
            yield item
    finally:
        # runs on normal exhaustion AND on generator close/abandonment
        stop.set()
        # the worker sees ``stop`` at the end of the fetch or the
        # hand-over it is in, and is not waited for here: the collector
        # finalizes an abandoned generator wherever it happens to run,
        # and a wait there could hold a lock the worker needs to get
        # that far
        while True:  # unblock a worker stuck in put()
            try:
                q.get_nowait()
            # drain-until-empty control flow, not a swallowed failure
            except queue.Empty:  # znicz-check: disable=ZNC008
                break


def _boundary(loader) -> tuple:
    """The loader's state and its shuffle stream's, read one after the
    other as ``Workflow.host_state()`` reads them (two reads, two
    copies: a snapshot pickles both, and one shared object would not
    serialize as two equal ones do)."""
    return loader.state_dict(), prng.get(loader.rand_name).state_dict()


class _EpochsWithoutEnd:
    """``loader.epoch()`` after ``loader.epoch()`` with an
    :class:`EpochEnd` between them, for one thread to pull and another
    to ``halt()``."""

    def __init__(self, loader):
        self._items = self._chain(loader)
        self._puller: Optional[threading.Thread] = None

    @staticmethod
    def _chain(loader) -> Iterator:
        while True:
            yield from loader.epoch()
            # epoch_number has moved on, the next reshuffle has not drawn
            yield EpochEnd(_boundary(loader))

    def __iter__(self):
        return self

    def __next__(self):
        self._puller = threading.current_thread()
        return next(self._items)

    def halt(self) -> None:
        """End every later pull, and wait for the thread that has
        pulled to finish (its consumer has told it to stop): on return
        nobody reads the loader and the producer's accounting is whole.
        In this order: a thread that names itself after the swap finds
        the chain ended."""
        self._items = iter(())
        if self._puller is not None:
            self._puller.join()


class CarriedEpochs:
    """One producer over a loader's epochs, for as long as the caller
    keeps asking for the next one.

    ``epoch()`` yields one epoch's (transformed) batches and returns at
    the boundary; meanwhile the producer is already into the next epoch.
    So while this object is live the loader and its shuffle stream are
    the PRODUCER's: ``boundary`` holds what they read at the last
    boundary the consumer reached (at the start, before the thread has
    drawn anything), which is what the loader itself would read there
    without a producer.  ``park()`` ends the arrangement: the producer
    is stopped and waited for (a rewind under a thread that is still
    drawing would race), the batches it had run ahead are dropped and
    the loader is put back to ``boundary``.  A caller that
    is dropped without parking takes the generator with it, which stops
    the thread (nothing here refers to the caller); the loader is then
    left where the producer stopped.
    """

    def __init__(self, loader, depth: int, transform: Callable):
        self._loader = loader
        self.boundary = _boundary(loader)
        self._source = _EpochsWithoutEnd(loader)
        # transform_stage=None: the workflow's placement closure times
        # itself (an H2DProbe); fetch and enqueue come from prefetch
        self._items = prefetch(
            self._source, depth, transform=transform, transform_stage=None
        )

    def epoch(self) -> Iterator:
        for item in self._items:
            if isinstance(item, EpochEnd):
                self.boundary = item.state
                return
            yield item
        raise PrefetchProducerError(
            "the prefetch producer ended inside an epoch (parked, or "
            "dead after an error that was already raised)"
        )

    def park(self) -> None:
        self._items.close()
        self._source.halt()
        self._loader.load_state_dict(self.boundary[0])
