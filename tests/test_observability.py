"""Unified telemetry tests: registry, tracer, phase timer, exports.

Covers the ISSUE 3 acceptance criteria end to end: registry unit
semantics (labels, cardinality cap, histogram bucket edges, Prometheus
text that a parser accepts), tracer nesting + valid Chrome-trace JSONL,
and the engine integration — one serve run through ``PagedDecodeEngine``
must yield a parseable ``/metrics`` exposition with non-zero
tokens/compile/latency series over real HTTP, and a trace whose
``serve/admit`` span count equals the requests processed, with the
registry counters cross-checked against ``compile_stats()`` and the
submitted request count.
"""

import functools
import http.server
import json
import logging
import math
import threading
import urllib.request
from collections import Counter

import numpy as np
import pytest

from znicz_tpu import observability as obs
from znicz_tpu.observability.phases import PhaseTimer
from znicz_tpu.observability.registry import (
    MetricsRegistry,
    parse_prometheus_text,
)
from znicz_tpu.observability.tracing import Tracer


def _series(name, **labels):
    """A (possibly absent) child series of the default registry."""
    m = obs.get_registry().metrics().get(name)
    if m is None:
        return None
    key = tuple(str(labels[n]) for n in m.labelnames)
    return m.children().get(key)


def _counter_value(name, **labels):
    child = _series(name, **labels)
    return 0.0 if child is None else child.value


def _counter_total(name):
    """Sum over every label set (e.g. retirements across reasons)."""
    m = obs.get_registry().metrics().get(name)
    if m is None:
        return 0.0
    return sum(c.value for c in m.children().values())


def _hist_count(name, **labels):
    child = _series(name, **labels)
    return 0 if child is None else child.count


# -- registry --------------------------------------------------------------


class TestRegistry:
    def test_counters_and_gauges(self):
        r = MetricsRegistry()
        c = r.counter("reqs_total", "requests", ("kind",))
        c.labels(kind="a").inc()
        c.labels(kind="a").inc(2)
        c.labels("b").inc()
        assert c.labels(kind="a").value == 3
        assert c.labels(kind="b").value == 1
        g = r.gauge("depth", "queue depth")
        g.set(4)
        g.inc()
        g.dec(2)
        assert g.value == 3
        with pytest.raises(ValueError):
            c.labels(kind="a").inc(-1)  # counters only go up

    def test_get_or_create_shares_and_conflicts(self):
        r = MetricsRegistry()
        a = r.counter("x_total", "first")
        b = r.counter("x_total", "again")
        assert a is b  # two subsystems share the series, no second ledger
        with pytest.raises(ValueError, match="already registered"):
            r.gauge("x_total")
        with pytest.raises(ValueError, match="already registered"):
            r.counter("x_total", labelnames=("k",))
        with pytest.raises(ValueError, match="invalid metric name"):
            r.counter("bad name")
        with pytest.raises(ValueError, match="invalid label"):
            r.counter("y_total", labelnames=("le",))

    def test_label_cardinality_capped(self):
        r = MetricsRegistry(max_series_per_metric=2)
        c = r.counter("x_total", "", ("k",))
        c.labels(k="1").inc()
        c.labels(k="2").inc()
        c.labels(k="1").inc()  # existing series: always fine
        with pytest.raises(ValueError, match="cardinality"):
            c.labels(k="3")

    def test_histogram_bucket_edges(self):
        # le semantics: a sample exactly AT an upper bound belongs to
        # that bucket; past the last finite edge lands in +Inf only
        r = MetricsRegistry()
        h = r.histogram("h_seconds", "", buckets=(1.0, 2.0))
        h.observe(1.0)
        h.observe(1.5)
        h.observe(5.0)
        child = r.metrics()["h_seconds"].children()[()]
        cum = dict(child.cumulative())
        assert cum[1.0] == 1 and cum[2.0] == 2 and cum[math.inf] == 3
        assert child.count == 3
        assert child.sum == pytest.approx(7.5)

    def test_histogram_quantile_estimates(self):
        r = MetricsRegistry()
        h = r.histogram("q_seconds", "", buckets=(0.1, 1.0, 10.0))
        for _ in range(99):
            h.observe(0.05)
        h.observe(5.0)
        child = r.metrics()["q_seconds"].children()[()]
        assert child.quantile(0.5) <= 0.1
        assert child.quantile(0.999) > 1.0
        empty = r.histogram("e_seconds", "", buckets=(1.0,))
        assert empty._default().quantile(0.5) is None

    def test_prometheus_text_parses(self):
        r = MetricsRegistry()
        r.counter("a_total", "with \"quotes\"", ("k",)).labels(
            k='va"l\\ue'
        ).inc(2)
        r.gauge("g", "gauge").set(-1.5)
        h = r.histogram("h_seconds", "hist", ("phase",), buckets=(0.1, 1))
        h.labels(phase="x").observe(0.5)
        text = r.prometheus_text()
        parsed = parse_prometheus_text(text)
        assert parsed["types"] == {
            "a_total": "counter", "g": "gauge", "h_seconds": "histogram"
        }
        samples = {
            (n, tuple(sorted(l.items()))): v
            for n, l, v in parsed["samples"]
        }
        assert samples[("a_total", (("k", 'va"l\\ue'),))] == 2
        assert samples[("g", ())] == -1.5
        assert samples[
            ("h_seconds_count", (("phase", "x"),))
        ] == 1
        # the real Prometheus client parser accepts it too, if present
        try:
            from prometheus_client.parser import (
                text_string_to_metric_families,
            )
        except ImportError:
            pass
        else:
            fams = {f.name: f for f in text_string_to_metric_families(text)}
            assert fams["h_seconds"].type == "histogram"

    def test_parser_rejects_garbage(self):
        with pytest.raises(ValueError):
            parse_prometheus_text("not a metric line at all!")
        with pytest.raises(ValueError):
            parse_prometheus_text("# TYPE x sometype\n")
        with pytest.raises(ValueError, match="le"):
            parse_prometheus_text(
                "# TYPE h histogram\nh_bucket 5\nh_count 5\n"
            )

    def test_snapshot_is_json_able(self):
        r = MetricsRegistry()
        r.counter("c_total", "c").inc(7)
        r.histogram("h_seconds", "h", buckets=(1.0,)).observe(0.5)
        snap = json.loads(json.dumps(r.snapshot()))
        assert snap["c_total"]["series"][0]["value"] == 7
        hseries = snap["h_seconds"]["series"][0]
        assert hseries["count"] == 1
        assert hseries["buckets"]["+Inf"] == 1
        assert hseries["p50"] is not None

    def test_reset_zeroes_but_keeps_registration(self):
        r = MetricsRegistry()
        c = r.counter("c_total", "c", ("k",))
        c.labels(k="a").inc(5)
        r.reset()
        assert c.labels(k="a").value == 0
        assert r.counter("c_total", "c", ("k",)) is c


# -- tracer ----------------------------------------------------------------


class TestTracer:
    def test_nesting_and_jsonl(self, tmp_path):
        path = tmp_path / "trace.jsonl"
        tr = Tracer()
        tr.start(path=str(path))
        with tr.span("outer", n=1):
            with tr.span("inner"):
                pass
        tr.instant("mark", note="x")
        events = tr.stop()
        by = {e["name"]: e for e in events}
        inner, outer = by["inner"], by["outer"]
        # the child completes first but nests inside the parent
        assert events[0]["name"] == "inner"
        assert inner["args"]["parent"] == "outer"
        assert outer["ts"] <= inner["ts"]
        assert (
            inner["ts"] + inner["dur"]
            <= outer["ts"] + outer["dur"] + 0.01
        )
        assert outer["args"]["n"] == 1
        assert by["mark"]["ph"] == "i"
        assert tr.span_counts() == Counter(outer=1, inner=1)
        # the streamed JSONL is line-for-line the event list
        lines = path.read_text().splitlines()
        assert len(lines) == len(events) == 3
        for line in lines:
            ev = json.loads(line)
            assert ev["ph"] in ("X", "i")
            assert {"name", "ts", "pid", "tid"} <= set(ev)

    def test_not_recording_is_noop(self):
        tr = Tracer()
        with tr.span("ghost"):
            pass
        assert tr.events() == []

    def test_memory_cap_does_not_truncate_file(self, tmp_path):
        # the in-memory buffer caps; the streamed JSONL stays complete
        path = tmp_path / "capped.jsonl"
        tr = Tracer(max_events=2)
        tr.start(path=str(path))
        for i in range(5):
            with tr.span(f"s{i}"):
                pass
        events = tr.stop()
        assert len(events) == 2 and tr.dropped == 3
        assert len(path.read_text().splitlines()) == 5

    def test_file_rotation_caps_disk_and_keeps_newest(self, tmp_path):
        # ISSUE 7 satellite: the streamed file is size-capped — it
        # rotates to <path>.1 instead of growing without bound on a
        # long-running server; the newest window stays in <path>
        path = tmp_path / "trace.jsonl"
        tr = Tracer()
        tr.start(path=str(path), max_file_bytes=400)
        for i in range(40):
            with tr.span(f"span-{i:03d}"):
                pass
        tr.stop()
        assert tr.rotations >= 1
        rotated = tmp_path / "trace.jsonl.1"
        assert rotated.exists()
        assert rotated.stat().st_size <= 400 + 200  # one line of slack
        # every line in both generations is valid JSONL, no torn writes
        names = []
        for p in (rotated, path):
            for line in p.read_text().splitlines():
                names.append(json.loads(line)["name"])
        # the newest span is in the live file; rotation loses only the
        # OLDEST generation (at most one cap's worth)
        assert json.loads(
            path.read_text().splitlines()[-1]
        )["name"] == "span-039"
        assert names == sorted(names)  # contiguous suffix, in order

    def test_doubly_failed_rotation_degrades_to_memory_buffer(
        self, tmp_path, monkeypatch
    ):
        # rename fails AND the append-reopen fails (dir deleted, EROFS):
        # the stream is lost, _file goes None — the NEXT span must land
        # in the in-memory buffer, not raise AttributeError on the
        # instrumented thread
        path = tmp_path / "doomed.jsonl"
        tr = Tracer()
        tr.start(path=str(path), max_file_bytes=120)

        def _boom(*a, **kw):
            raise OSError("gone")

        monkeypatch.setattr("znicz_tpu.observability.tracing.os.replace",
                            _boom)
        monkeypatch.setattr("builtins.open", _boom)
        for i in range(20):
            with tr.span(f"s{i}"):
                pass
        monkeypatch.undo()
        events = tr.stop()
        assert [e["name"] for e in events[-3:]] == ["s17", "s18", "s19"]

    def test_rotation_disabled_streams_unbounded(self, tmp_path):
        path = tmp_path / "unbounded.jsonl"
        tr = Tracer()
        tr.start(path=str(path), max_file_bytes=None)
        for i in range(50):
            with tr.span(f"s{i}"):
                pass
        tr.stop()
        assert tr.rotations == 0
        assert len(path.read_text().splitlines()) == 50

    def test_shutdown_gracefully_flushes_the_tracer(self, tmp_path):
        # run_server's SIGTERM path calls shutdown_gracefully, which
        # must stop a recording tracer so the JSONL is flushed/closed
        from znicz_tpu.observability import get_tracer
        from znicz_tpu.services import serve as serve_mod

        path = tmp_path / "drain.jsonl"
        tracer = get_tracer()
        tracer.start(path=str(path))
        try:
            with tracer.span("final-request"):
                pass
            server = serve_mod.build_server(
                directory=str(tmp_path), port=0
            )
            threading.Thread(
                target=server.serve_forever, daemon=True
            ).start()
            serve_mod.shutdown_gracefully(server)
            server.server_close()
            assert tracer.recording is False
            lines = path.read_text().splitlines()
            assert any(
                json.loads(ln)["name"] == "final-request"
                for ln in lines
            )
        finally:
            if tracer.recording:
                tracer.stop()

    def test_start_twice_raises_and_write_jsonl(self, tmp_path):
        tr = Tracer()
        tr.start()
        with pytest.raises(RuntimeError):
            tr.start()
        with tr.span("a"):
            pass
        tr.stop()
        out = tmp_path / "later.jsonl"
        tr.write_jsonl(str(out))
        assert json.loads(out.read_text().splitlines()[0])["name"] == "a"


# -- phase timer -----------------------------------------------------------


class TestPipelineSpans:
    """ROADMAP observability next-rung: snapshot writes and the loader
    prefetch producer thread must appear on the Perfetto timeline."""

    def test_snapshot_and_prefetch_producer_spans(self, tmp_path):
        from znicz_tpu.loader.prefetch import prefetch
        from znicz_tpu.workflow.snapshotter import Snapshotter

        tr = obs.get_tracer()
        tr.start()
        try:
            snap = Snapshotter(str(tmp_path), compress=False)
            snap.save(
                {"w": np.zeros((2, 2), np.float32)}, {"epoch": 1},
                tag="best",
            )
            out = list(prefetch(iter(range(5)), depth=2))
        finally:
            events = tr.stop()
        assert out == list(range(5))
        counts = Counter(
            e["name"] for e in events if e.get("ph") == "X"
        )
        assert counts["snapshot/save"] == 1
        assert counts["snapshot/gather"] == 1
        assert counts["snapshot/write"] == 1
        # the produce span is stage-split (PR 13): one fetch span per
        # item + the final end-of-stream pull
        assert counts["loader/fetch"] == 6
        # gather/write nest inside the save span
        write = next(e for e in events if e["name"] == "snapshot/write")
        assert write["args"]["parent"] == "snapshot/save"
        # producer spans carry the WORKER thread's tid — their own
        # Perfetto track, next to (not under) the consumer's spans
        prod = [
            e for e in events if e["name"] == "loader/fetch"
        ]
        assert all(e["tid"] != threading.get_ident() for e in prod)

    def test_snapshot_save_untraced_still_writes(self, tmp_path):
        # spans must be pure observation: with the tracer idle the save
        # path writes the same file
        from znicz_tpu.workflow.snapshotter import Snapshotter, load_snapshot

        snap = Snapshotter(str(tmp_path), compress=False)
        path = snap.save(
            {"w": np.ones((2,), np.float32)}, {"epoch": 2}, tag="best"
        )
        state, host = load_snapshot(path)
        np.testing.assert_array_equal(state["w"], np.ones((2,)))
        assert host == {"epoch": 2}


class TestPhaseTimer:
    def test_summary_is_windowed_over_shared_series(self):
        r = MetricsRegistry()
        tr = Tracer()
        t1 = PhaseTimer("p_seconds", registry=r, tracer=tr)
        with t1.phase("a"):
            pass
        with t1.phase("a"):
            pass
        with t1.phase("b"):
            pass
        s = t1.summary()
        assert s["a"]["count"] == 2 and s["b"]["count"] == 1
        assert s["a"]["total_s"] >= 0 and "mean_ms" in s["a"]
        # a second instance on the SAME metric starts a fresh window...
        t2 = PhaseTimer("p_seconds", registry=r, tracer=tr)
        assert t2.summary() == {}
        with t2.phase("a"):
            pass
        assert t2.summary()["a"]["count"] == 1
        # ...while the first keeps counting from ITS baseline and the
        # registry holds the process-lifetime truth
        assert t1.summary()["a"]["count"] == 3
        assert r.metrics()["p_seconds"].children()[("a",)].count == 3
        t1.reset()
        assert t1.summary() == {}

    def test_phase_emits_span_with_args(self):
        r = MetricsRegistry()
        tr = Tracer()
        t = PhaseTimer("p_seconds", registry=r, tracer=tr, span_prefix="w/")
        tr.start()
        with t.phase("c", tag=7):
            pass
        events = tr.stop()
        assert events[0]["name"] == "w/c"
        assert events[0]["args"]["tag"] == 7


# -- bounded latency stats (satellite) -------------------------------------


class TestLatencyStats:
    def test_ring_bound_and_p99(self):
        from znicz_tpu.utils.profiling import LatencyStats

        seen = []
        ls = LatencyStats(max_samples=4, observe=seen.append)
        for v in [1.0] * 6 + [0.001] * 4:
            ls.record(v)
        # lifetime count survives the bound; the observer saw every one
        assert len(ls) == 10 and len(seen) == 10
        s = ls.summary()
        assert s["count"] == 10
        # percentiles describe the retained window (the last 4 samples)
        assert s["p99_ms"] == pytest.approx(1.0)
        assert s["max_ms"] == pytest.approx(1.0)
        ls.reset()
        assert ls.summary() == {"count": 0}

    def test_summary_has_all_percentile_keys(self):
        from znicz_tpu.utils.profiling import LatencyStats

        ls = LatencyStats()
        ls.record(0.25)
        assert {"count", "mean_ms", "p50_ms", "p95_ms", "p99_ms",
                "max_ms"} <= set(ls.summary())

    def test_rejects_bad_capacity(self):
        from znicz_tpu.utils.profiling import LatencyStats

        with pytest.raises(ValueError):
            LatencyStats(max_samples=0)


# -- idempotent logging setup (satellite) ----------------------------------


class TestSetupLogging:
    def test_existing_handlers_survive_unless_forced(self):
        from znicz_tpu.core import logger as L

        root = logging.getLogger()
        saved_handlers = root.handlers[:]
        saved_level = root.level
        saved_flag = L._configured
        try:
            marker = logging.NullHandler()
            root.handlers[:] = [marker]
            root.setLevel(logging.WARNING)
            L._configured = False
            L.setup_logging()  # pre-configured root: must not clobber
            assert root.handlers == [marker]
            # ...but a default-WARNING root must not eat INFO logs
            assert root.level == logging.INFO
            # a deliberately-verbose root is never QUIETED
            root.setLevel(logging.DEBUG)
            L.setup_logging()
            assert root.level == logging.DEBUG
            L.setup_logging(force=True)  # explicit escape hatch
            assert root.handlers != [marker]
            assert len(root.handlers) == 1
            installed = root.handlers[:]
            L.setup_logging()  # repeat call: idempotent
            assert root.handlers == installed
        finally:
            root.handlers[:] = saved_handlers
            root.setLevel(saved_level)
            L._configured = saved_flag


# -- status writer export surface (satellite + tentpole) -------------------


class _StubDecision:
    epoch = 2
    max_epochs = 3
    best_value = 0.1
    best_epoch = 1
    history = [1, 2]


class _StubWorkflow:
    name = "stub"
    decision = _StubDecision()
    timer = None


_VERDICT = {
    "improved": False,
    "stop": False,
    "summary": {"train": {"n_samples": 8, "loss": 0.5, "err_pct": 2.0}},
}


class TestStatusWriterTelemetry:
    def test_snapshot_embedded_and_writes_atomic(self, tmp_path):
        from znicz_tpu.services.web_status import StatusWriter

        obs.counter(
            "znicz_test_status_total", "status-writer test series"
        ).inc(3)
        w = StatusWriter(str(tmp_path))
        w.on_epoch(_StubWorkflow(), _VERDICT)
        status = json.loads((tmp_path / "status.json").read_text())
        assert status["epoch"] == 1
        snap = status["metrics"]
        assert (
            snap["znicz_test_status_total"]["series"][0]["value"] >= 3
        )
        # the Prometheus twin parses, and no temp files leak (atomic
        # replace means a poller can never read a truncated file)
        parsed = parse_prometheus_text(
            (tmp_path / "metrics.prom").read_text()
        )
        assert "znicz_test_status_total" in parsed["types"]
        assert not [p for p in tmp_path.iterdir() if ".tmp." in p.name]
        assert "metrics registry snapshot" in (
            tmp_path / "status.html"
        ).read_text()


# -- /metrics endpoint -----------------------------------------------------


def _serve_dir(directory):
    from znicz_tpu.services.serve import StatusRequestHandler

    handler = functools.partial(
        StatusRequestHandler, directory=str(directory)
    )
    srv = http.server.ThreadingHTTPServer(("127.0.0.1", 0), handler)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    return srv


def _get(srv, path):
    port = srv.server_address[1]
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=10
    ) as resp:
        return resp.read().decode(), resp.headers.get("Content-Type")


class TestMetricsEndpoint:
    def test_prefers_training_written_files(self, tmp_path):
        # both endpoints must read the TRAINING process's exports when
        # present — never one from the file and one from the live
        # registry (a dashboard would see contradictory worlds)
        (tmp_path / "metrics.prom").write_text(
            "# TYPE from_training counter\nfrom_training 42\n"
        )
        (tmp_path / "status.json").write_text(
            json.dumps({"metrics": {"from_training": {
                "type": "counter", "help": "",
                "series": [{"labels": {}, "value": 42}],
            }}})
        )
        srv = _serve_dir(tmp_path)
        try:
            body, ctype = _get(srv, "/metrics")
            jbody, _ = _get(srv, "/metrics.json")
        finally:
            srv.shutdown()
        assert "from_training 42" in body
        assert ctype.startswith("text/plain")
        parse_prometheus_text(body)
        snap = json.loads(jbody)
        assert snap["from_training"]["series"][0]["value"] == 42

    def test_json_derives_from_prom_when_status_lacks_metrics(
        self, tmp_path
    ):
        # metrics.prom alone (older StatusWriter, crash between writes):
        # /metrics.json must derive from the SAME file /metrics serves,
        # never fall back to the serve process's unrelated registry
        (tmp_path / "metrics.prom").write_text(
            "# TYPE from_training counter\nfrom_training 42\n"
        )
        srv = _serve_dir(tmp_path)
        try:
            jbody, _ = _get(srv, "/metrics.json")
        finally:
            srv.shutdown()
        snap = json.loads(jbody)
        assert snap["from_training"]["series"][0]["value"] == 42
        assert snap["from_training"]["type"] == "counter"

    def test_falls_back_to_live_registry_and_json(self, tmp_path):
        obs.counter(
            "znicz_test_endpoint_total", "endpoint test series"
        ).inc()
        srv = _serve_dir(tmp_path)  # no metrics.prom in the directory
        try:
            body, _ = _get(srv, "/metrics")
            jbody, jtype = _get(srv, "/metrics.json")
        finally:
            srv.shutdown()
        assert "znicz_test_endpoint_total" in parse_prometheus_text(
            body
        )["types"]
        assert jtype == "application/json"
        assert "znicz_test_endpoint_total" in json.loads(jbody)


# -- engine integration: the acceptance criteria ---------------------------


EOS = 14
HEADS = 4


def _params():
    from znicz_tpu.core import prng
    from znicz_tpu.workflow.transformer import init_lm_params

    prng.seed_all(27)
    # vocab 19: a geometry no OTHER test file uses, so the process-wide
    # first-compile ledger is cold and the registry compile delta below
    # cross-checks EXACTLY against this engine's n_programs
    return init_lm_params(19, 32, 2, HEADS, max_seq=64)


class TestEngineTelemetry:
    def test_serve_run_feeds_registry_tracer_and_metrics_endpoint(
        self, tmp_path
    ):
        from znicz_tpu.services.engine import PagedDecodeEngine

        params = _params()
        base = {
            "submitted": _counter_value(
                "znicz_serve_requests_submitted_total"
            ),
            "admitted": _counter_value(
                "znicz_serve_requests_admitted_total"
            ),
            "retired": _counter_total(
                "znicz_serve_requests_retired_total"
            ),
            "tokens": _counter_value("znicz_serve_tokens_generated_total"),
            "compiles": _counter_total("znicz_serve_compiles_total"),
            "latency": _hist_count("znicz_serve_request_latency_seconds"),
            "ttft": _hist_count("znicz_serve_ttft_seconds"),
        }
        gen = np.random.default_rng(3)
        prompts = [
            gen.integers(0, 17, (n,)).astype(np.int32) for n in (5, 12, 3)
        ]
        trace_path = tmp_path / "serve.trace.jsonl"
        tracer = obs.get_tracer()
        tracer.start(path=str(trace_path))
        try:
            eng = PagedDecodeEngine(
                params, n_heads=HEADS, eos_id=EOS, batch_size=2,
                admit_every=4,
            )
            for p in prompts:
                eng.submit(p, max_new_tokens=5)
            comps = eng.run()
        finally:
            events = tracer.stop()
        n = len(prompts)
        assert len(comps) == n
        new_tokens = sum(c.n_new for c in comps)

        # (b) Chrome-trace JSONL: span counts match requests processed
        counts = Counter(e["name"] for e in events if e["ph"] == "X")
        assert counts["serve/admit"] == n
        assert counts["serve/decode"] >= 1
        lines = trace_path.read_text().splitlines()
        assert len(lines) == len(events) > 0
        for line in lines:
            ev = json.loads(line)
            assert {"name", "ph", "ts", "pid", "tid"} <= set(ev)

        # registry counters cross-check against the engine's own ledgers
        assert (
            _counter_value("znicz_serve_requests_submitted_total")
            - base["submitted"]
        ) == n
        assert (
            _counter_value("znicz_serve_requests_admitted_total")
            - base["admitted"]
        ) == n
        assert (
            _counter_total("znicz_serve_requests_retired_total")
            - base["retired"]
        ) == n
        assert (
            _counter_value("znicz_serve_tokens_generated_total")
            - base["tokens"]
        ) == new_tokens == eng.stats()["generated_tokens"]
        assert (
            _counter_total("znicz_serve_compiles_total")
            - base["compiles"]
        ) == eng.compile_stats()["n_programs"]
        assert (
            _hist_count("znicz_serve_request_latency_seconds")
            - base["latency"]
        ) == n
        assert (
            _hist_count("znicz_serve_ttft_seconds") - base["ttft"]
        ) == n
        assert _counter_value(
            "znicz_serve_queue_depth"
        ) == 0 and _counter_value("znicz_serve_active_slots") == 0

        # a SECOND engine with the same geometry rides the shared jit
        # caches — the process-wide compile counter must not re-count
        compiles_after = _counter_total("znicz_serve_compiles_total")
        eng2 = PagedDecodeEngine(
            params, n_heads=HEADS, eos_id=EOS, batch_size=2,
            admit_every=4,
        )
        eng2.submit(prompts[0], max_new_tokens=3)
        eng2.run()
        assert eng2.compile_stats()["n_programs"] == 2
        assert (
            _counter_total("znicz_serve_compiles_total") == compiles_after
        )

        # (a) /metrics over real HTTP: parseable, with non-zero
        # tokens / compile / latency series
        srv = _serve_dir(tmp_path)  # no metrics.prom: live registry
        try:
            body, ctype = _get(srv, "/metrics")
        finally:
            srv.shutdown()
        assert ctype.startswith("text/plain")
        parsed = parse_prometheus_text(body)
        samples = {}
        for name, labels, value in parsed["samples"]:
            samples[name] = samples.get(name, 0.0) + value
        assert samples["znicz_serve_tokens_generated_total"] >= new_tokens
        assert samples["znicz_serve_compiles_total"] > 0
        assert samples["znicz_serve_request_latency_seconds_count"] >= n
        try:
            from prometheus_client.parser import (
                text_string_to_metric_families,
            )
        except ImportError:
            pass
        else:
            assert list(text_string_to_metric_families(body))


def _documented_serve_families(path):
    """Every ``znicz_serve_*`` family a document names, a ``{a,b}`` inside
    a name written out (``_prefix_{hits,misses}_total``); a ``{...}`` that
    ends a name lists its labels and is dropped."""
    import re

    with open(path) as f:
        text = f.read()
    names = set()
    for m in re.finditer(
        r"(znicz_serve_[a-z_]*)(?:\{([a-z_,]+)\}([a-z_]+))?", text
    ):
        stem, choices, rest = m.groups()
        if choices:
            names.update(stem + c + rest for c in choices.split(","))
        elif stem != "znicz_serve_":  # the prose's "znicz_serve_*"
            names.add(stem)
    return names


@pytest.mark.parametrize("doc", ["docs/SERVING.md", "docs/OBSERVABILITY.md"])
def test_every_documented_serve_family_is_registered(doc):
    # a row must not outlive what it describes: one request through the
    # front door registers every family the serving path owns
    import os

    from znicz_tpu.services import PagedDecodeEngine, ServingFrontDoor

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    documented = _documented_serve_families(os.path.join(repo, doc))
    assert len(documented) > 20, documented
    params = _params()
    with ServingFrontDoor(
        lambda: PagedDecodeEngine(
            params, n_heads=HEADS, eos_id=EOS, batch_size=2, block_size=8
        )
    ) as door:
        door.submit(np.arange(1, 12, dtype=np.int32), 5).result(timeout=60)
    assert documented <= set(obs.get_registry().metrics())
