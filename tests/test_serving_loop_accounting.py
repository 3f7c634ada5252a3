"""The serving thread accounts for its own time (PR 38).

The stages of ``pipeline.serving_loop_clock`` tile one turn of the front
door and the engine (their laps sum to the iterations exactly, through
``run()`` and behind a ``ServingFrontDoor``), every stage of the table is
observed, a stage is a span too (nested in its parent where the parent's
extent holds it, on the profiler's annotations with the tracer idle), the
decode period and the prefill chunks between two decode chunks are
observed only while rows decode, and the parents ``serve/admit``,
``serve/prefill``, ``serve/decode`` and ``serve/verify`` are what they
were.
"""

import time

import numpy as np
import pytest

from test_input_accounting import annotations  # noqa: F401  (fixture)
from znicz_tpu.core import prng
from znicz_tpu.observability import get_registry, get_tracer, pipeline
from znicz_tpu.services import PagedDecodeEngine, ServingFrontDoor
from znicz_tpu.workflow.transformer import init_lm_params

BLOCK, CHUNK, EOS = 8, 4, 16
PERIODS = "znicz_serve_decode_period_seconds"
BETWEEN = "znicz_serve_prefill_chunks_between_decodes"
IN_PARENT = ("prepare", "dispatch", "wait", "fetch")


@pytest.fixture(scope="module")
def params():
    """A toy LM that never ends a sequence: the end-of-sequence column
    of its head is column 0's, and an argmax takes the first of equals,
    so every answer runs to its budget and a test knows its chunks."""
    prng.seed_all(27)
    tree = init_lm_params(17, 32, 2, 4, max_seq=128)
    head = tree[-1]["head"]
    tree[-1]["head"] = head.at[:, EOS].set(head[:, 0])
    return tree


def _engine(params, **kw):
    kw = {
        "n_heads": 4, "eos_id": EOS, "batch_size": 2, "block_size": BLOCK,
        "admit_every": CHUNK, "max_seq": 128, **kw,
    }
    return PagedDecodeEngine(params, **kw)


def _prompt(seed, length):
    gen = np.random.default_rng(seed)
    return gen.integers(0, 16, (length,)).astype(np.int32)


def _read():
    """What the tests compare, as plain numbers of the process-wide
    registry (other files' engines observed there too: take deltas)."""
    fams = get_registry().metrics()

    def hist(name):
        m = fams.get(name)
        if m is None:
            return {}
        return {k: (c.count, c.sum) for k, c in m.children().items()}

    def value(name):
        m = fams.get(name)
        return sum(c.value for c in m.children().values()) if m else 0.0

    return {
        "stages": {
            k[0]: v for k, v in hist(pipeline.SERVE_LOOP_STAGE_METRIC).items()
        },
        "turns": hist(pipeline.SERVE_LOOP_ITERATION_METRIC).get((), (0, 0.0)),
        "periods": hist(PERIODS).get((), (0, 0.0)),
        "between": hist(BETWEEN).get((), (0, 0.0)),
        "prefill_chunks": value("znicz_serve_prefill_chunks_total"),
        "preemptions": value("znicz_serve_preemptions_total"),
        "phases": {
            k[0]: v for k, v in hist("znicz_serve_phase_seconds").items()
        },
    }


def _minus(after, before):
    def pair(a, b):
        return (a[0] - b[0], a[1] - b[1])

    out = {}
    for key, now in after.items():
        was = before[key]
        if isinstance(now, dict):
            out[key] = {
                k: pair(v, was.get(k, (0, 0.0))) for k, v in now.items()
            }
            out[key] = {k: v for k, v in out[key].items() if v[0]}
        elif isinstance(now, tuple):
            out[key] = pair(now, was)
        else:
            out[key] = now - was
    return out


def _serve(params, prompts, new_tokens, *, door, **kw):
    """The window's delta of one engine serving ``prompts``."""
    before = _read()
    if door:
        with ServingFrontDoor(lambda: _engine(params, **kw)) as front:
            handles = [front.submit(p, new_tokens) for p in prompts]
            for h in handles:
                assert h.result(timeout=120.0).finish_reason == "budget"
    else:
        eng = _engine(params, **kw)
        for p in prompts:
            eng.submit(p, new_tokens)
        assert len(eng.run()) == len(prompts)
    return _minus(_read(), before)


class TestStagesTileTheTurn:
    @pytest.mark.parametrize("door", [False, True], ids=["run", "frontdoor"])
    def test_the_laps_sum_to_the_iterations(self, params, door):
        got = _serve(
            params, [_prompt(i, 5 + 9 * i) for i in range(3)], 9, door=door,
        )
        turns, wall = got["turns"]
        assert turns > 0
        staged = sum(s for _, s in got["stages"].values())
        assert staged == pytest.approx(wall, rel=1e-9)
        front = {k for k in got["stages"] if k.startswith("frontdoor/")}
        assert front == (
            {
                "frontdoor/control", "frontdoor/pump", "frontdoor/stream",
                "frontdoor/housekeeping",
            } if door else {"frontdoor/housekeeping"}
        )
        # one lap a turn where a stage runs once a turn; housekeeping
        # twice behind a door (the engine's residency, the door's gauges)
        assert got["stages"]["serve/schedule"][0] == turns
        assert got["stages"]["frontdoor/housekeeping"][0] == (
            2 * turns if door else turns
        )

    def test_a_turn_without_work_books_nothing(self, params):
        before = _read()
        with ServingFrontDoor(
            lambda: _engine(params), idle_tick_s=0.005
        ) as front:
            first, give_up = front._last_tick, time.monotonic() + 5.0
            while front._last_tick == first and time.monotonic() < give_up:
                time.sleep(0.005)
            assert front._last_tick != first  # an idle turn went by
        got = _minus(_read(), before)
        assert got["turns"] == (0, 0.0) and got["stages"] == {}

    def test_every_stage_of_the_table_is_observed(self, params):
        # chunked prefill (prompts of 3 blocks), a preemption (the pool
        # holds one row's growth, not two), the front door's stages
        tight = _serve(
            params, [_prompt(i, 20) for i in range(3)], 24, door=True,
            n_blocks=10, prefix_cache=False,
        )
        # a second engine drafts from a repeating prompt and verifies
        drafted = _serve(
            params, [np.tile(np.arange(1, 5, dtype=np.int32), 6)], 16,
            door=False, spec_k=4, n_blocks=64,
        )
        assert tight["preemptions"] > 0
        seen = set(tight["stages"]) | set(drafted["stages"])
        assert seen == set(pipeline.SERVE_LOOP_STAGES)
        assert tight["stages"]["serve/prefill/host"][0] >= 9  # 3 x 3 chunks
        assert tight["stages"]["serve/prefill/wait"][0] >= 3
        assert any(k.startswith("serve/verify/") for k in drafted["stages"])


class TestStagesAreSpans:
    def test_children_nest_inside_their_parents(self, params):
        tracer = get_tracer()
        tracer.start()
        try:
            _serve(params, [_prompt(1, 20), _prompt(2, 5)], 9, door=False)
        finally:
            events = [e for e in tracer.stop() if e.get("ph") == "X"]
        by_name = {}
        for e in events:
            by_name.setdefault(e["name"], []).append(e)

        def inside(child, parents):
            lo, hi = child["ts"], child["ts"] + child["dur"]
            return any(
                p["ts"] <= lo and hi <= p["ts"] + p["dur"] + 1e-3
                and p["tid"] == child["tid"]
                for p in parents
            )

        chunks = by_name["serve/prefill"] + by_name["serve/admit"]
        for name in ("serve/prefill/host", "serve/prefill/wait"):
            assert by_name[name]
            for e in by_name[name]:
                assert e["args"]["parent"] in ("serve/prefill", "serve/admit")
                assert inside(e, chunks)
        assert len(by_name["serve/prefill/host"]) == len(chunks)
        # the wait is the admission's alone
        assert len(by_name["serve/prefill/wait"]) == len(by_name["serve/admit"])
        for part in IN_PARENT:
            spans = by_name[f"serve/decode/{part}"]
            assert len(spans) == len(by_name["serve/decode"])
            for e in spans:
                assert e["args"]["parent"] == "serve/decode"
                assert inside(e, by_name["serve/decode"])
        # grow and emit lie around the parent, whose extent is kept
        for part in ("grow", "emit"):
            for e in by_name[f"serve/decode/{part}"]:
                assert e.get("args", {}).get("parent") != "serve/decode"
                assert not inside(e, by_name["serve/decode"])

    def test_stages_reach_the_annotation_with_the_tracer_idle(
        self, params, annotations  # noqa: F811
    ):
        assert not get_tracer().recording
        before = get_tracer().events()
        _serve(params, [_prompt(1, 20)], 9, door=True)
        _serve(
            params, [np.tile(np.arange(1, 5, dtype=np.int32), 6)], 16,
            door=False, spec_k=4, n_blocks=64,
        )
        assert set(pipeline.SERVE_LOOP_STAGES) <= set(annotations)
        assert {"serve/admit", "serve/decode", "serve/verify"} <= set(
            annotations
        )
        assert get_tracer().events() == before


class TestTheGapAClientSees:
    def test_n_chunks_observe_n_minus_one_periods_and_none_across_idle(
        self, params
    ):
        eng = _engine(params, batch_size=1)
        for seed in (1, 2):
            before = _read()
            eng.submit(_prompt(seed, 5), 4 * CHUNK + 2)
            eng.run()  # and then the engine stands idle
            got = _minus(_read(), before)
            chunks = got["stages"]["serve/decode/wait"][0]
            assert chunks >= 4
            assert got["periods"][0] == chunks - 1
            assert got["between"] == (chunks - 1, 0.0)
            # each period is a turn of the thread, wait to wait
            assert got["periods"][1] <= got["turns"][1]

    def test_a_chunk_that_retires_every_row_starts_no_period(self, params):
        # two answers of one chunk each, one after the other in one slot:
        # busy throughout, and no two chunks with rows decoding between
        before = _read()
        eng = _engine(params, batch_size=1)
        for seed in (1, 2):
            eng.submit(_prompt(seed, 5), CHUNK + 1)
        eng.run()
        got = _minus(_read(), before)
        assert got["stages"]["serve/decode/wait"][0] == 2
        assert got["periods"][0] == 0

    def test_between_decodes_sums_to_the_prefill_chunks_of_a_busy_stretch(
        self, params
    ):
        eng = _engine(params, prefill_budget=BLOCK)  # one chunk a tick
        eng.submit(_prompt(1, 5), 60)
        # the first answer decodes (two chunks: a period has begun) ...
        for _ in range(3):
            assert eng.tick()
        assert eng.active == 1
        before = _read()
        # ... when a prompt of four chunks arrives and prefills beside it
        eng.submit(_prompt(2, 4 * BLOCK - 3), 6)
        eng.run()
        got = _minus(_read(), before)
        assert got["prefill_chunks"] == 4
        assert got["between"][1] == got["prefill_chunks"]
        assert got["between"][0] == got["periods"][0]
        assert got["periods"][0] == got["stages"]["serve/decode/wait"][0]


class TestTheParentsAreWhatTheyWere:
    def test_counts_and_arguments(self, params):
        tracer = get_tracer()
        before = _read()
        tracer.start()
        try:
            with ServingFrontDoor(lambda: _engine(params)) as front:
                handles = [
                    front.submit(_prompt(i, 5 + 9 * i), 9) for i in range(3)
                ]
                done = [h.result(timeout=120.0) for h in handles]
        finally:
            events = [e for e in tracer.stop() if e.get("ph") == "X"]
        got = _minus(_read(), before)
        admits = [e for e in events if e["name"] == "serve/admit"]
        prefills = [e for e in events if e["name"] == "serve/prefill"]
        decodes = [e for e in events if e["name"] == "serve/decode"]
        assert len(admits) == 3  # one a request
        assert sorted(e["args"]["trace"] for e in admits) == sorted(
            c.trace_id for c in done
        )
        for e in admits + prefills:
            assert {"request", "bucket", "chunk", "trace", "instance"} <= set(
                e["args"]
            )
        # 5, 14 and 23 prompt tokens at a block of 8: 1 + 2 + 3 chunks
        assert len(admits) + len(prefills) == 6
        assert got["phases"]["admit"][0] == 3
        assert got["phases"]["prefill"][0] == 3
        assert got["phases"]["decode"][0] == len(decodes)
        assert got["stages"]["serve/decode/wait"][0] == len(decodes)
        for e in decodes:
            assert {"active", "traces", "instance"} <= set(e["args"])
        # the parent's seconds hold its four children and nothing else
        # (prepare's lap opens a little ahead of the parent: loose)
        inner = sum(
            got["stages"][f"serve/decode/{part}"][1] for part in IN_PARENT
        )
        assert inner == pytest.approx(got["phases"]["decode"][1], rel=0.25)
