"""ServingFrontDoor: streaming, deadlines, cancel, shed, watchdog.

The front door's contract (docs/SERVING.md "The front door"): every
accepted request resolves to exactly one typed completion — eos/budget
from the engine, cancelled / deadline_exceeded / error / shed from the
robustness layer — with its stream terminated and, on the paged
backend, its blocks reclaimed (free == pool after every scenario).  No
failure path is theoretical here: each is forced deterministically via
the :mod:`znicz_tpu.utils.faults` injection points and asserted
against non-faulted ``generate()`` goldens for the survivors, plus the
zero-new-compiled-programs invariant across watchdog restarts.
"""

import time

import jax.numpy as jnp
import numpy as np
import pytest

from znicz_tpu import observability as obs
from znicz_tpu.core import prng
from znicz_tpu.services import (
    EngineClosedError,
    PagedDecodeEngine,
    RejectedError,
    RequestTooLargeError,
    ServingFrontDoor,
)
from znicz_tpu.utils import faults
from znicz_tpu.workflow import generate as G
from znicz_tpu.workflow.transformer import init_lm_params

EOS = 14
HEADS = 4
T_MAX = 64
BS = 8


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.clear()
    yield
    faults.clear()


@pytest.fixture(scope="module")
def params():
    prng.seed_all(27)
    return init_lm_params(17, 32, 2, HEADS, max_seq=T_MAX)


@pytest.fixture(scope="module", autouse=True)
def _warm(params):
    """Compile the engine programs ONCE before any timing-sensitive
    test: the first-compile seconds must not eat a deadline budget."""
    eng = _engine_factory(params)()
    gen = np.random.default_rng(3)
    for n in (5, 12):
        eng.submit(gen.integers(0, 17, (n,)).astype(np.int32), 12)
    eng.run()


def _engine_factory(params, **kw):
    kw.setdefault("n_heads", HEADS)
    kw.setdefault("eos_id", EOS)
    kw.setdefault("batch_size", 2)
    kw.setdefault("block_size", BS)
    kw.setdefault("max_seq", T_MAX)
    kw.setdefault("admit_every", 4)

    def factory():
        return PagedDecodeEngine(params, **kw)

    return factory


def _reference(params, prompt, budget, eos=EOS):
    out = np.asarray(
        G.generate(
            params, jnp.asarray(prompt)[None], n_heads=HEADS,
            max_new_tokens=budget, eos_id=eos,
        )
    )[0]
    new = out[len(prompt):]
    hit = np.where(new == eos)[0]
    if len(hit):
        new = new[: hit[0] + 1]
    return np.concatenate([prompt, new])


def _prompts(n, seed=7):
    gen = np.random.default_rng(seed)
    return [
        gen.integers(0, 17, (k,)).astype(np.int32)
        for k in (5, 12, 3, 9, 17)[:n]
    ]


def _long_prompt(params, budget=40, seed=21):
    """A prompt whose greedy generation does NOT hit EOS within
    ``budget`` — the deterministic victim for cancel/deadline/crash
    tests (a natural EOS mid-test would win the race)."""
    gen = np.random.default_rng(seed)
    for _ in range(200):
        p = gen.integers(0, 17, (6,)).astype(np.int32)
        ref = _reference(params, p, budget)
        if len(ref) - len(p) == budget and ref[-1] != EOS:
            return p
    raise AssertionError("no EOS-free prompt found in 200 draws")


def _pool_swept(door):
    st = door.engine.stats()
    return st["pool_blocks_free"] == st["pool_blocks"]


def _wait_until(cond, timeout=10.0, what="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return
        time.sleep(0.005)
    raise AssertionError(f"timed out waiting for {what}")


def _labeled_sum(name):
    m = obs.get_registry().metrics().get(name)
    if m is None:
        return 0.0
    return sum(c.value for c in m.children().values())


def _hist_count(name):
    m = obs.get_registry().metrics().get(name)
    if m is None:
        return 0
    return sum(c.count for c in m.children().values())


def _retired_errors():
    m = obs.get_registry().metrics().get(
        "znicz_serve_requests_retired_total"
    )
    if m is None:
        return 0.0
    return sum(
        c.value for key, c in m.children().items()
        if key and key[0] == "error"
    )


def _paged_compiles_total():
    m = obs.get_registry().metrics().get("znicz_serve_compiles_total")
    if m is None:
        return 0.0
    return sum(
        c.value for key, c in m.children().items()
        if key[0] in ("prefill", "paged_chunk", "cow")
    )


class TestStreaming:
    def test_tokens_stream_and_goldens_match_generate(self, params):
        prompts = _prompts(3)
        budgets = [6, 4, 8]
        with ServingFrontDoor(_engine_factory(params)) as door:
            handles = [
                door.submit(p, b) for p, b in zip(prompts, budgets)
            ]
            streamed = [list(h.tokens(timeout=30.0)) for h in handles]
            for h, p, b, toks in zip(handles, prompts, budgets, streamed):
                comp = h.result(timeout=30.0)
                assert comp.finish_reason in ("eos", "budget")
                assert comp.trace_id == h.id
                np.testing.assert_array_equal(
                    comp.tokens, _reference(params, p, b)
                )
                # the stream is the completion's tail, token for token
                assert toks == list(comp.tokens[len(p):])
            assert len({h.id for h in handles}) == 3  # distinct trace ids
            assert _pool_swept(door)
            st = door.stats()
            assert st["submitted"] == 3 and st["completed"] == 3


    def test_handle_result_timeout_raises(self, params):
        with ServingFrontDoor(
            _engine_factory(params), engine_queue_limit=0
        ) as door:
            h = door.submit(_prompts(1)[0], 4)  # parked: nothing pumps
            with pytest.raises(TimeoutError):
                h.result(timeout=0.05)
            with pytest.raises(TimeoutError):
                next(h.tokens(timeout=0.05))


class TestAdmission:
    def test_validation_rejects_before_enqueue(self, params):
        with ServingFrontDoor(_engine_factory(params)) as door:
            with pytest.raises(ValueError, match="empty prompt"):
                door.submit([], 4)
            with pytest.raises(RequestTooLargeError, match="positional window"):
                door.submit([1, 2, 3], 10_000)
            # malformed prompt/deadline surface as ValueError at the
            # caller — a str deadline must never reach the engine
            # thread, where the per-tick expiry compare would wedge it
            with pytest.raises(ValueError, match="malformed prompt"):
                door.submit(None, 4)
            with pytest.raises(ValueError, match="malformed prompt"):
                door.submit([[1, 2], [3]], 4)
            with pytest.raises(ValueError, match="malformed deadline"):
                door.submit([1, 2], 4, deadline_s="soon")
            with pytest.raises(ValueError, match="deadline_s >= 0"):
                door.submit([1, 2], 4, deadline_s=-1.0)
            # typed subclass keeps legacy except ValueError working
            assert issubclass(RequestTooLargeError, ValueError)
            assert door.stats()["submitted"] == 0  # nothing enqueued

    def test_queue_full_sheds_with_retry_after(self, params):
        before = _labeled_sum("znicz_serve_rejected_total")
        with ServingFrontDoor(
            _engine_factory(params), max_pending=2, engine_queue_limit=0
        ) as door:
            p = _prompts(1)[0]
            door.submit(p, 4)
            door.submit(p, 4)
            with pytest.raises(RejectedError) as exc:
                door.submit(p, 4)
            assert exc.value.reason == "queue_full"
            assert exc.value.retry_after_s > 0
            assert door.stats()["rejected"] == {"queue_full": 1}
        assert _labeled_sum("znicz_serve_rejected_total") > before

    def test_pool_pressure_watermark_sheds(self, params):
        with ServingFrontDoor(
            _engine_factory(params),
            engine_queue_limit=0,
            shed_pool_frac=2.0,  # every pool state is "under pressure"
        ) as door:
            p = _prompts(1)[0]
            door.submit(p, 4)  # no backlog yet: accepted
            with pytest.raises(RejectedError) as exc:
                door.submit(p, 4)
            assert exc.value.reason == "pool_pressure"


class TestCancellation:
    def test_cancel_before_admission(self, params):
        with ServingFrontDoor(
            _engine_factory(params), engine_queue_limit=0
        ) as door:
            h = door.submit(_prompts(1)[0], 8)
            assert h.cancel() is True
            comp = h.result(timeout=10.0)
            assert comp.finish_reason == "cancelled"
            assert comp.n_new == 0
            assert list(h.tokens(timeout=5.0)) == []  # stream terminated

    def test_cancel_during_decode_reclaims_blocks(self, params):
        pa = _long_prompt(params)  # EOS-free for the full 40 budget
        pb = _prompts(2)[1]
        # slow ticks: the 40-token victim needs >= 10 ticks x 50 ms,
        # so the cancel deterministically lands mid-decode
        faults.inject("frontdoor.slow_tick", delay=0.05)
        with ServingFrontDoor(_engine_factory(params)) as door:
            ha = door.submit(pa, 40)  # long-running victim
            hb = door.submit(pb, 5)  # unaffected neighbor
            it = ha.tokens(timeout=30.0)
            next(it)  # decoding for sure
            assert ha.cancel() is True
            comp = ha.result(timeout=30.0)
            faults.clear()
            assert comp.finish_reason == "cancelled"
            assert 1 <= comp.n_new < 40
            # the neighbor sharing the pool stays golden
            np.testing.assert_array_equal(
                hb.result(timeout=30.0).tokens, _reference(params, pb, 5)
            )
            _wait_until(
                lambda: _pool_swept(door), what="block reclamation"
            )
            assert door.stats()["cancelled"] == 1

    def test_cancel_after_completion_is_noop(self, params):
        with ServingFrontDoor(_engine_factory(params)) as door:
            h = door.submit(_prompts(1)[0], 3)
            h.result(timeout=30.0)
            assert h.cancel() is False
            assert door.stats()["cancelled"] == 0


class TestDeadlines:
    def test_deadline_expires_while_queued(self, params):
        with ServingFrontDoor(
            _engine_factory(params), engine_queue_limit=0
        ) as door:
            h = door.submit(_prompts(1)[0], 8, deadline_s=0.01)
            comp = h.result(timeout=10.0)
            assert comp.finish_reason == "deadline_exceeded"
            assert comp.n_new == 0

    def test_deadline_expires_mid_decode(self, params):
        # slow ticks make expiry deterministic: a 40-token budget needs
        # ~10 ticks x >=50 ms >> the 250 ms deadline, and the first
        # tick (admission + first chunk) lands well inside it
        faults.inject("frontdoor.slow_tick", delay=0.05)
        with ServingFrontDoor(_engine_factory(params)) as door:
            h = door.submit(_long_prompt(params), 40, deadline_s=0.25)
            comp = h.result(timeout=30.0)
            faults.clear()
            assert comp.finish_reason == "deadline_exceeded"
            assert 1 <= comp.n_new < 40  # expired MID-decode
            _wait_until(
                lambda: _pool_swept(door), what="block reclamation"
            )
            assert door.stats()["deadline_exceeded"] == 1

    def test_default_deadline_applies(self, params):
        with ServingFrontDoor(
            _engine_factory(params),
            engine_queue_limit=0,
            default_deadline_s=0.01,
        ) as door:
            comp = door.submit(_prompts(1)[0], 8).result(timeout=10.0)
            assert comp.finish_reason == "deadline_exceeded"


class TestWatchdog:
    def test_engine_crash_fails_inflight_readmits_queued(self, params):
        # batch_size=1: A occupies the slot, B sits in the ENGINE
        # queue, C waits at the front door.  A decode-step crash must
        # fail ONLY A (typed error), rebuild the engine, re-admit B and
        # leave C untouched — both then golden-match generate() — and
        # recompile NOTHING (the jit caches survive the restart).
        pa = _long_prompt(params, budget=30)
        pb, pc = _prompts(3)[1:]
        factory = _engine_factory(params, batch_size=1, admit_every=2)
        # pre-compile this factory's whole program ladder (prefill +
        # every x2 window rung pa can reach — the paged_chunk key is
        # per (admit_every, batch_size), so the module _warm doesn't
        # cover it): the zero-new-compiles pin below must measure only
        # restart-caused compiles, not a rung the stream itself happens
        # to touch for the first time after the snapshot (a race on how
        # far A has decoded when the crash lands)
        warm = factory()
        warm.submit(pa, 30)
        warm.run()
        # slow ticks: A's 30-token budget spans >= 15 ticks x 50 ms, so
        # the crash deterministically lands while A is still decoding
        faults.inject("frontdoor.slow_tick", delay=0.05)
        with ServingFrontDoor(factory, engine_queue_limit=1) as door:
            ha = door.submit(pa, 30)
            next(ha.tokens(timeout=30.0))  # A is decoding
            hb = door.submit(pb, 5)
            hc = door.submit(pc, 5)
            _wait_until(
                lambda: door.watchdog_state()["inflight"] == 2,
                what="B pumped into the engine queue",
            )
            engine_before = door.engine
            compiles_before = _paged_compiles_total()
            lat_before = _hist_count(
                "znicz_serve_frontdoor_latency_seconds"
            )
            err_before = _retired_errors()
            faults.inject(
                "engine.decode_step", exc=RuntimeError("boom"), times=1
            )
            ca = ha.result(timeout=30.0)
            faults.clear("frontdoor.slow_tick")
            assert ca.finish_reason == "error"
            assert "boom" in ca.error
            # the dead engine's REAL per-request accounting rides the
            # error completion: A was mid-decode when the engine
            # crashed, so its breakdown must say so — not the
            # never-reached-the-engine fallback's 100% queue wait
            assert ca.timings["decode_s"] > 0
            assert ca.timings["prefill_s"] > 0
            for h, p in ((hb, pb), (hc, pc)):
                comp = h.result(timeout=60.0)
                assert comp.finish_reason in ("eos", "budget")
                np.testing.assert_array_equal(
                    comp.tokens, _reference(params, p, 5)
                )
            st = door.stats()
            assert st["watchdog_restarts"] == 1
            assert door.engine is not engine_before
            # crash-failed A is NOT a latency measurement (its 'time to
            # crash' would dilute the SLO histogram mid-incident); only
            # B and C land in the client-clock latency series.  A IS an
            # error: retired{reason=error} must tick so /slo error_rate
            # sees the incident
            assert (
                _hist_count("znicz_serve_frontdoor_latency_seconds")
                - lat_before
                == 2
            )
            assert _retired_errors() - err_before == 1.0
            # watchdog restarts ride the warm jit caches: zero new
            # compiled programs, pinned via znicz_serve_compiles_total
            assert _paged_compiles_total() == compiles_before
            assert _pool_swept(door)

    def test_allocator_failure_is_survivable(self, params):
        with ServingFrontDoor(_engine_factory(params)) as door:
            faults.inject(
                "pool.alloc", exc=RuntimeError("alloc boom"), times=1
            )
            comp = door.submit(_prompts(1)[0], 4).result(timeout=30.0)
            assert comp.finish_reason == "error"
            assert "alloc boom" in comp.error
            assert door.stats()["watchdog_restarts"] == 1
            # the rebuilt engine serves normally
            p = _prompts(2)[1]
            comp2 = door.submit(p, 5).result(timeout=30.0)
            np.testing.assert_array_equal(
                comp2.tokens, _reference(params, p, 5)
            )
            assert _pool_swept(door)

    def test_pool_exhaustion_expires_typed_then_recovers(self, params):
        # persistent simulated exhaustion: allocation always reports
        # the pool dry, so the request livelocks bind -> starve ->
        # self-preempt until its DEADLINE retires it (typed, no hang,
        # no leak); once pressure clears the door serves again
        with ServingFrontDoor(_engine_factory(params)) as door:
            faults.inject("pool.pressure", flag=True)
            comp = door.submit(
                _prompts(1)[0], 4, deadline_s=0.3
            ).result(timeout=30.0)
            assert comp.finish_reason == "deadline_exceeded"
            faults.clear()
            p = _prompts(2)[1]
            comp2 = door.submit(p, 5).result(timeout=30.0)
            np.testing.assert_array_equal(
                comp2.tokens, _reference(params, p, 5)
            )
            assert _pool_swept(door)
            assert door.stats()["watchdog_restarts"] == 0  # no crash

    def test_stall_detection_flips_health(self, params):
        with ServingFrontDoor(
            _engine_factory(params), stall_after_s=0.1
        ) as door:
            assert door.healthy()
            faults.inject("frontdoor.slow_tick", delay=0.6, times=1)
            _wait_until(
                lambda: door.watchdog_state()["state"] == "stalled",
                timeout=5.0,
                what="stall detection",
            )
            assert not door.healthy()
            _wait_until(
                lambda: door.watchdog_state()["state"] == "running",
                timeout=5.0,
                what="stall recovery",
            )


class TestShutdown:
    def test_close_drains_then_sheds_with_typed_completions(self, params):
        door = ServingFrontDoor(
            _engine_factory(params), engine_queue_limit=0
        )
        h1 = door.submit(_prompts(1)[0], 4)
        h2 = door.submit(_prompts(1)[0], 4)
        door.close(grace_s=0.1)  # parked work cannot drain: shed
        for h in (h1, h2):
            comp = h.result(timeout=5.0)
            assert comp.finish_reason == "shed"
            assert list(h.tokens(timeout=2.0)) == []
        assert door.stats()["shed"] == 2
        with pytest.raises(EngineClosedError):
            door.submit(_prompts(1)[0], 4)
        assert door.watchdog_state()["state"] == "closed"

    def test_close_is_idempotent_and_drains_live_work(self, params):
        door = ServingFrontDoor(_engine_factory(params))
        p = _prompts(1)[0]
        h = door.submit(p, 5)
        door.close(grace_s=30.0)
        comp = h.result(timeout=5.0)
        np.testing.assert_array_equal(
            comp.tokens, _reference(params, p, 5)
        )
        door.close()  # second close is a no-op


class TestCompileBudget:
    def test_frontdoor_adds_zero_compiled_programs(self, params):
        # twin streams: once through a bare engine, once through the
        # front door — the registry's first-compile ledger must not
        # move for the front-door run (it reuses the same prefill /
        # decode-chunk programs; deadline/cancel/watchdog machinery is
        # host-side only)
        prompts, budgets = _prompts(3), [6, 4, 8]
        eng = _engine_factory(params)()
        for p, b in zip(prompts, budgets):
            eng.submit(p, b)
        eng.run()
        before = _paged_compiles_total()
        with ServingFrontDoor(_engine_factory(params)) as door:
            handles = [
                door.submit(p, b) for p, b in zip(prompts, budgets)
            ]
            for h in handles:
                h.result(timeout=30.0)
            ledger = door.engine.compile_stats()["programs"]
        assert _paged_compiles_total() == before
        assert {k[0] for k in ledger} <= {"prefill", "paged_chunk", "cow"}


_TIMING_KEYS = {
    "queue_s", "prefill_s", "decode_s", "preemptions", "cached_tokens",
    "spec_drafted", "spec_accepted",
}


class TestRequestTimings:
    def test_every_completion_carries_the_breakdown(self, params):
        prompts, budgets = _prompts(3), [6, 4, 8]
        with ServingFrontDoor(_engine_factory(params)) as door:
            handles = [
                door.submit(p, b) for p, b in zip(prompts, budgets)
            ]
            for h in handles:
                comp = h.result(timeout=30.0)
                assert comp.timings is not None
                assert set(comp.timings) == _TIMING_KEYS
                assert comp.timings["queue_s"] >= 0.0
                # an admitted request did real prefill + decode work
                assert comp.timings["prefill_s"] > 0.0
                assert comp.timings["decode_s"] > 0.0
            recent = door.recent_requests()
        assert len(recent) == 3
        assert recent[0]["timings"] is not None  # newest first
        assert {r["trace_id"] for r in recent} == {h.id for h in handles}

    def test_prefill_dominated_vs_queue_dominated_golden(self, params):
        # the acceptance golden: "why was this request slow" must have
        # two distinguishable answers.  (1) one long prompt, budget 1:
        # all prefill, no queue wait.  (2) a request parked behind a
        # busy single-slot engine: all queue wait, one chunk of prefill.
        long_p = np.arange(48, dtype=np.int32) % 16 + 1
        eng = _engine_factory(params, batch_size=1)()
        rid = eng.submit(long_p, 1)
        eng.run()
        t = eng.completions[rid].timings
        assert t["prefill_s"] > t["queue_s"]
        assert t["decode_s"] == 0.0  # retired at admission

        eng = _engine_factory(params, batch_size=1)()
        first = eng.submit(_long_prompt(params), 40)
        second = eng.submit(_prompts(1)[0], 2)
        eng.run()
        t2 = eng.completions[second].timings
        # the second request sat queued through the first's whole
        # 40-token decode: waiting dwarfs its own prefill
        assert t2["queue_s"] > t2["prefill_s"]
        assert t2["queue_s"] > eng.completions[first].timings["queue_s"]

    def test_preemption_and_cache_counts_land_in_timings(self, params):
        # pool pressure: 2 slots, a pool too small for both -> the
        # younger is preempted and recomputed; its breakdown says so
        factory = _engine_factory(
            params, batch_size=2, n_blocks=2 * (40 // BS) - 1,
            prefix_cache=False,
        )
        eng = factory()
        a = eng.submit(_long_prompt(params), 28)
        b = eng.submit(_long_prompt(params, seed=22), 28)
        eng.run()
        timings = [eng.completions[r].timings for r in (a, b)]
        assert sum(t["preemptions"] for t in timings) >= 1
        # prefix reuse: same prompt twice -> the second's cached_tokens
        eng2 = _engine_factory(params)()
        p = np.arange(2 * BS, dtype=np.int32) % 16 + 1
        r1 = eng2.submit(p, 3)
        eng2.run()
        r2 = eng2.submit(p, 3)
        eng2.run()
        assert eng2.completions[r1].timings["cached_tokens"] == 0
        assert eng2.completions[r2].timings["cached_tokens"] > 0

    def test_queued_termination_is_pure_queue_time(self, params):
        with ServingFrontDoor(
            _engine_factory(params), engine_queue_limit=0
        ) as door:
            h = door.submit(_prompts(1)[0], 4)  # parked forever
            time.sleep(0.05)
            h.cancel()
            comp = h.result(timeout=30.0)
        assert comp.finish_reason == "cancelled"
        assert comp.timings["queue_s"] >= 0.05
        assert comp.timings["prefill_s"] == 0.0
        assert comp.timings["decode_s"] == 0.0

    def test_trace_id_reaches_engine_spans_and_instants(self, params):
        tracer = obs.get_tracer()
        tracer.start()
        try:
            with ServingFrontDoor(_engine_factory(params)) as door:
                h = door.submit(_prompts(1)[0], 4)
                h.result(timeout=30.0)
                tid = h.id
        finally:
            events = tracer.stop()
        admits = [
            e for e in events
            if e["name"] == "serve/admit"
            and e.get("args", {}).get("trace") == tid
        ]
        assert len(admits) == 1
        lifecycle = {
            e["name"] for e in events
            if e.get("args", {}).get("trace") == tid
        }
        assert "serve/queued" in lifecycle
        assert "serve/retired" in lifecycle

    def test_debug_ring_is_bounded(self, params):
        with ServingFrontDoor(
            _engine_factory(params), debug_requests=2
        ) as door:
            handles = [door.submit(_prompts(1)[0], 2) for _ in range(4)]
            for h in handles:
                h.result(timeout=30.0)
            recent = door.recent_requests()
        assert len(recent) == 2
        assert recent[0]["trace_id"] == handles[-1].id  # newest first


class TestSLOEndpointBehavior:
    def test_slo_breach_under_injected_latency_then_recovery(
        self, params
    ):
        # the acceptance path: fault-injected slow ticks push TTFT over
        # a tight threshold -> burn rate breaches in EVERY window; after
        # the fault clears, fast requests wash the short window clean ->
        # breach clears (multi-window AND), p99s visibly recover
        from znicz_tpu.observability.slo import SLOTarget

        reg = obs.get_registry()
        with ServingFrontDoor(
            _engine_factory(params),
            slo_targets=(
                SLOTarget(
                    "ttft", "znicz_serve_frontdoor_ttft_seconds",
                    0.15, 0.9,
                ),
            ),
            slo_windows_s=(0.6, 120.0),
            slo_sample_gap_s=0.0,
        ) as door:
            mon = door._slo
            mon.sample()  # pristine baseline before any traffic
            with faults.injected("frontdoor.slow_tick", delay=0.3):
                for _ in range(3):
                    door.submit(_prompts(1)[0], 2).result(timeout=30.0)
            snap = door.slo_snapshot()
            assert snap["targets"]["ttft"]["breached"] is True
            assert snap["breached"] is True
            slow_p99 = snap["targets"]["ttft"]["windows"]["120"]["p99_s"]
            assert slow_p99 is not None and slow_p99 > 0.15
            # recovery: fault cleared, let the short window age out the
            # slow samples, then run fast traffic
            mon.sample()
            time.sleep(0.7)
            for _ in range(6):
                door.submit(_prompts(1)[0], 2).result(timeout=30.0)
            snap = door.slo_snapshot()
            short = snap["targets"]["ttft"]["windows"]["0.6"]
            assert short["n"] >= 6
            assert short["burn_rate"] < 1.0
            assert snap["targets"]["ttft"]["breached"] is False

    def test_observability_paths_add_zero_compiled_programs(self, params):
        # the host-side observability machinery (slo snapshot, debug
        # ring, aggregator push/merge of the live registry) must not
        # touch the compile ledger or the jit caches
        from znicz_tpu.observability.aggregate import MetricsAggregator

        prompts, budgets = _prompts(3), [6, 4, 8]
        with ServingFrontDoor(_engine_factory(params)) as door:
            for p, b in zip(prompts, budgets):
                door.submit(p, b).result(timeout=30.0)
            eng = door.engine
            ledger_before = dict(eng.compile_stats()["programs"])
            jit_before = {
                k: v for k, v in eng.compile_stats().items()
                if k.endswith("_jit_entries")
            }
            compiles_before = _paged_compiles_total()
            for _ in range(3):
                door.slo_snapshot()
                door.recent_requests()
            agg = MetricsAggregator()
            agg.push("self", obs.get_registry().snapshot())
            agg.push("twin", text=obs.get_registry().prometheus_text())
            agg.merged_snapshot()
            agg.prometheus_text()
            door.submit(prompts[0], budgets[0]).result(timeout=30.0)
            stats = eng.compile_stats()
        assert stats["programs"] == ledger_before
        assert {
            k: v for k, v in stats.items()
            if k.endswith("_jit_entries")
        } == jit_before
        assert _paged_compiles_total() == compiles_before


class TestFaultsHarness:
    def test_times_bounds_fires(self):
        faults.inject("x.y", exc=RuntimeError("q"), times=2)
        for _ in range(2):
            with pytest.raises(RuntimeError):
                faults.fire("x.y")
        assert faults.fire("x.y") is False  # auto-disarmed

    def test_flag_and_delay_points(self):
        faults.inject("p.q", flag=True)
        assert faults.fire("p.q") is True
        faults.clear("p.q")
        assert faults.fire("p.q") is False
        t0 = time.monotonic()
        faults.inject("s.t", delay=0.05, times=1)
        assert faults.fire("s.t") is True
        assert time.monotonic() - t0 >= 0.05

    def test_injected_scope_clears_even_on_raise(self):
        with pytest.raises(faults.FaultInjected):
            with faults.injected("a.b", times=5):
                faults.fire("a.b")
        assert faults.fire("a.b") is False

    def test_env_spec_parses_and_rejects_garbage(self):
        faults._parse_env("m.n:times=1:delay=0.0,o.p:flag")
        assert faults.armed("m.n") and faults.fire("o.p") is True
        faults.clear()
        with pytest.raises(ValueError, match="unknown field"):
            faults._parse_env("q.r:bogus=1")


class TestSpeculativeFrontDoor:
    """ISSUE 12 plumb-through: a spec_k factory serves through the
    front door on the same tick loop — streams stay golden, the
    completion timings carry the spec tallies, and a watchdog restart
    rebuilds a SPECULATING engine from the factory."""

    def test_spec_engine_streams_golden_with_timings(self, params):
        with ServingFrontDoor(
            _engine_factory(params, spec_k=7), max_pending=8
        ) as door:
            prompts = _prompts(3)
            handles = [door.submit(p, 16) for p in prompts]
            for h, p in zip(handles, prompts):
                toks = list(h.tokens(timeout=30.0))
                comp = h.result(timeout=5.0)
                ref = _reference(params, p, 16)
                assert np.array_equal(comp.tokens, ref)
                assert toks == list(ref[len(p):])
                assert "spec_drafted" in comp.timings
                assert "spec_accepted" in comp.timings
            st = door.stats()["engine"]["spec"]
            assert st["enabled"] and st["k"] == 7
            assert st["drafted"] == st["accepted"] + st["rejected"]

    def test_watchdog_restart_preserves_spec_config(self, params):
        with ServingFrontDoor(
            _engine_factory(params, spec_k=7), max_pending=8
        ) as door:
            p = _long_prompt(params)
            with faults.injected(
                "engine.decode_step", exc=RuntimeError("chip fell over"),
                times=1,
            ):
                h = door.submit(p, 40)
                comp = h.result(timeout=30.0)
            assert comp.finish_reason == "error"
            _wait_until(
                lambda: door.engine is not None
                and door.engine.spec_k == 7,
                what="rebuilt spec engine",
            )
            # the rebuilt engine speculates and stays golden
            h2 = door.submit(p, 12)
            assert np.array_equal(
                h2.result(timeout=30.0).tokens,
                _reference(params, p, 12),
            )
            assert door.stats()["watchdog_restarts"] == 1

    def test_factory_of_a_tower_without_verify_fails_construction(
        self, params
    ):
        from znicz_tpu.services import SpeculationUnsupportedError

        class NoVerifyTower:
            """A tower of another kind: it brings no verify program."""

        def bad_factory():
            return PagedDecodeEngine(
                params, n_heads=HEADS, eos_id=EOS, spec_k=4,
                model=NoVerifyTower(),
            )

        with pytest.raises(SpeculationUnsupportedError):
            ServingFrontDoor(bad_factory, max_pending=4)
