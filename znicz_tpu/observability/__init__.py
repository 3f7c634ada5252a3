"""Unified telemetry: metrics registry, span tracer, export surfaces.

One process-wide substrate replacing the per-subsystem ledgers that
had accumulated by PR 2 (engine LatencyStats + compile dict, the
generate serve-cache counters, StatusWriter's timing dict):

* **Registry** (:mod:`registry`) — labeled counters / gauges /
  histograms with a fixed bucket ladder; Prometheus text exposition
  (``/metrics`` on ``python -m znicz_tpu.services.serve``,
  ``metrics.prom`` beside ``status.json``) and JSON snapshots
  (``status.json``, bench records).
* **Tracer** (:mod:`tracing`) — nested host spans emitted as Chrome
  trace-event JSONL (open in https://ui.perfetto.dev), wrapping
  ``jax.profiler.TraceAnnotation`` so host spans line up with device
  captures.
* **PhaseTimer** (:mod:`phases`) — StepTimer-compatible phase timing
  that feeds both.

* **Fleet aggregation** (:mod:`aggregate`) — a MetricsAggregator
  service replicas push registry snapshots to (instance-tagged,
  TTL-expired, bucket-wise histogram merge) plus the MetricsPusher
  background thread feeding it.
* **Fleet tracing** (:mod:`collector`) — the tracing twin: a
  TraceCollector spans push to (TracePusher), merged into ONE
  Perfetto-loadable timeline at ``GET /trace`` with pid=instance.
* **Device/compile telemetry** (:mod:`device`) — the program ledger
  behind ``/debug/programs`` (compile wall time, cost analysis,
  executable memory per true first compile) and on-demand
  ``jax.profiler`` captures.
* **SLO monitoring** (:mod:`slo`) — rolling-window p50/p95/p99 and
  multi-window burn rates over declared targets (``/slo``,
  ``tools/znicz-slo``).
* **Pipeline attribution** (:mod:`pipeline`) — per-stage input-pipeline
  timings (fetch / host_transform / h2d / enqueue), the live H2D
  bandwidth gauge, and the step-wall decomposition behind
  ``tools/znicz-doctor``.
* **Step anomaly flight recorder** (:mod:`anomaly`) — typed per-step
  verdicts (non-finite loss/grad, loss spikes, step-time regressions)
  with a bounded ring of last-K-steps snapshots, surfaced through
  ``status.json`` / ``/metrics`` / the aggregator.

Convenience module-level ``counter``/``gauge``/``histogram`` operate on
the default registry; see docs/OBSERVABILITY.md for the metric catalog.
Pure stdlib at import time — jax is only touched lazily by the tracer.
"""

from znicz_tpu.observability.aggregate import (  # noqa: F401
    MetricsAggregator,
    MetricsPusher,
    build_aggregator_server,
)
from znicz_tpu.observability.collector import (  # noqa: F401
    TraceCollector,
    TracePusher,
    build_collector_server,
)
from znicz_tpu.observability import device  # noqa: F401
from znicz_tpu.observability.anomaly import (  # noqa: F401
    StepAnomalyDetector,
)
from znicz_tpu.observability.phases import PhaseTimer  # noqa: F401
from znicz_tpu.observability.pipeline import (  # noqa: F401
    H2DProbe,
    PipelineAttribution,
)
from znicz_tpu.observability.registry import (  # noqa: F401
    DEFAULT_TIME_BUCKETS,
    Metric,
    MetricsRegistry,
    fraction_le,
    get_registry,
    parse_prometheus_text,
    quantile_from_cumulative,
)
from znicz_tpu.observability.slo import (  # noqa: F401
    DEFAULT_TARGETS,
    SLOMonitor,
    SLOTarget,
)
from znicz_tpu.observability.tracing import (  # noqa: F401
    Tracer,
    get_tracer,
    instant,
    span,
)


def counter(name: str, help: str = "", labelnames=()) -> Metric:
    """Get-or-create a counter on the default registry."""
    return get_registry().counter(name, help, labelnames)


def gauge(name: str, help: str = "", labelnames=()) -> Metric:
    """Get-or-create a gauge on the default registry."""
    return get_registry().gauge(name, help, labelnames)


def histogram(
    name: str, help: str = "", labelnames=(), buckets=DEFAULT_TIME_BUCKETS
) -> Metric:
    """Get-or-create a histogram on the default registry."""
    return get_registry().histogram(name, help, labelnames, buckets)


def prometheus_text() -> str:
    """Prometheus text exposition of the default registry."""
    return get_registry().prometheus_text()


def snapshot() -> dict:
    """JSON-able snapshot of the default registry."""
    return get_registry().snapshot()
