"""Paged KV-cache engine: goldens, block pool, preemption, compiles.

The engine (`services.engine.PagedDecodeEngine`) must be a
TRANSPARENT batching layer: every
completion's tokens equal the single-request ``generate()`` output for
that prompt (up to EOS), through chunked prefill, lazy block
allocation, block reuse after retirement, and preemption-with-
recompute under pool pressure.  And the whole stream must stay
recompile-free on ONE prefill program (the [1, block_size] chunk —
every prompt length) plus a logarithmic x2 ladder of decode-chunk
variants keyed by the active block-window rung — verified against the
engine's ledger, the process-wide jit caches, AND the
``znicz_serve_compiles_total`` registry counter (the ISSUE 4 CI
criterion: zero recompiles after warmup across a growth-past-one-block
stream).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from znicz_tpu import observability as obs
from znicz_tpu.core import prng
from znicz_tpu.services.engine import PagedDecodeEngine
from znicz_tpu.workflow import generate as G
from znicz_tpu.workflow.transformer import init_lm_params

EOS = 14
HEADS = 4
T_MAX = 64
BS = 8  # block size under test (buckets irrelevant on the paged path)


def _params(seed=27, max_seq=T_MAX):
    prng.seed_all(seed)
    return init_lm_params(17, 32, 2, HEADS, max_seq=max_seq)


def _reference(params, prompt, budget, eos=EOS):
    """Single-request greedy generate(), trimmed at (and including) the
    first EOS — what the engine promises each request, paging aside."""
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


def _engine(params, **kw):
    kw.setdefault("n_heads", HEADS)
    kw.setdefault("eos_id", EOS)
    kw.setdefault("batch_size", 2)
    kw.setdefault("block_size", BS)
    kw.setdefault("max_seq", T_MAX)
    kw.setdefault("admit_every", 4)
    return PagedDecodeEngine(params, **kw)


def _compiles_total():
    """Registry sum of znicz_serve_compiles_total over the PAGED kinds."""
    m = obs.get_registry().metrics().get("znicz_serve_compiles_total")
    if m is None:
        return 0.0
    return sum(
        c.value for key, c in m.children().items()
        if key[0] in ("prefill", "paged_chunk")
    )


def _counter_value(name):
    m = obs.get_registry().metrics().get(name)
    return 0.0 if m is None else m.value


def _hist_count(name):
    m = obs.get_registry().metrics().get(name)
    child = None if m is None else m.children().get(())
    return 0 if child is None else child.count


class TestPagedGoldens:
    @pytest.mark.parametrize(
        "seed, lengths, budgets, kw",
        [
            # 5 ragged requests through 2 slots: lengths 5 and 3 pad
            # inside one block, 12 and 17 span multiple chunks
            (7, (5, 12, 3, 9, 17), (6, 4, 8, 5, 7), {}),
            # the tier-1 smoke: two mixed-length requests at the
            # constructor's own block size
            (3, (5, 12), (6, 5), {"block_size": 16}),
            # the same five through 16-token blocks, admitting every 3
            (7, (5, 12, 3, 9, 17), (6, 4, 8, 5, 7),
             {"block_size": 16, "admit_every": 3}),
            # three budget-1 requests retire AT admission: the slot
            # must go on to the next queued request, not sit out a chunk
            (17, (4, 6, 8, 5, 7), (1, 1, 1, 6, 5), {"block_size": 16}),
        ],
    )
    def test_mixed_lengths_golden(self, seed, lengths, budgets, kw):
        # ragged requests through 2 slots: slot reuse, chunked prefill
        # and the shared pool must all stay invisible
        params = _params()
        gen = np.random.default_rng(seed)
        prompts = [
            gen.integers(0, 17, (n,)).astype(np.int32) for n in lengths
        ]
        eng = _engine(params, **kw)
        ids = [eng.submit(p, b) for p, b in zip(prompts, budgets)]
        assert eng.pending == len(prompts)
        comps = eng.run()
        assert len(comps) == len(prompts)
        assert eng.pending == 0 and eng.active == 0
        for p, b, rid in zip(prompts, budgets, ids):
            np.testing.assert_array_equal(
                eng.completions[rid].tokens, _reference(params, p, b)
            )
        # every block returned to the pool at retirement
        st = eng.stats()
        assert st["kv_backend"] == "paged"
        assert st["pool_blocks_free"] == st["pool_blocks"]
        assert st["preemptions"] == 0
        c = comps[0]
        assert c.latency_s > 0 and c.tokens_per_sec > 0
        assert eng.latency.summary()["count"] == len(prompts)
        assert st["generated_tokens"] == sum(c.n_new for c in comps)
        assert set(st["phases"]) >= {"admit", "decode"}

    def test_long_prompt_prefills_in_chunks(self):
        # a 17-token prompt pads to 24 = 3 chunks of the ONE compiled
        # prefill program; the chunk counter proves the interleaving
        # unit actually ran per-block
        params = _params()
        gen = np.random.default_rng(9)
        p = gen.integers(0, 17, (17,)).astype(np.int32)
        chunks0 = _counter_value("znicz_serve_prefill_chunks_total")
        eng = _engine(params)
        rid = eng.submit(p, 5)
        eng.run()
        np.testing.assert_array_equal(
            eng.completions[rid].tokens, _reference(params, p, 5)
        )
        chunks1 = _counter_value("znicz_serve_prefill_chunks_total")
        assert chunks1 - chunks0 == 3

    @pytest.mark.parametrize(
        "seed, length, block", [(13, 6, BS), (19, 11, 16)]
    )
    def test_budget_one_and_immediate_eos_retire_at_admit(
        self, seed, length, block
    ):
        params = _params()
        gen = np.random.default_rng(seed)
        p = gen.integers(0, 17, (length,)).astype(np.int32)
        eng = _engine(params, block_size=block)
        rid = eng.submit(p, 1)
        (comp,) = eng.run()
        assert comp.id == rid and comp.n_new == 1
        assert comp.finish_reason in ("eos", "budget")
        np.testing.assert_array_equal(
            comp.tokens, _reference(params, p, 1)
        )
        assert eng.stats()["pool_blocks_free"] == eng.usable_blocks

    @pytest.mark.parametrize(
        "seed, lengths, kw",
        [
            (11, (4, 10, 6), {}),
            (23, (9, 3, 17, 5), {"block_size": 16, "batch_size": 3}),
        ],
    )
    def test_sampling_mode_deterministic_and_in_vocab(
        self, seed, lengths, kw
    ):
        # same rng + same submission order -> identical streams; tokens
        # stay in-vocab under temperature sampling
        params = _params()
        gen = np.random.default_rng(seed)
        prompts = [
            gen.integers(0, 17, (n,)).astype(np.int32) for n in lengths
        ]

        def serve():
            eng = _engine(
                params, admit_every=3, temperature=0.9,
                rng=jax.random.key(8), **kw,
            )
            ids = [eng.submit(p, 5) for p in prompts]
            eng.run()
            return [eng.completions[i].tokens for i in ids]

        a, b = serve(), serve()
        for ta, tb, p in zip(a, b, prompts):
            np.testing.assert_array_equal(ta, tb)
            new = ta[len(p):]
            assert (new >= 0).all() and (new < 17).all()
            assert 1 <= len(new) <= 5


class TestBlockPool:
    def test_retire_frees_and_readmit_reuses_blocks(self):
        # white-box allocator check: a retired request's blocks return
        # to the pool and the next admission reuses them (LIFO free
        # list) instead of fragmenting toward fresh blocks.  Prefix
        # cache OFF: with it on, retired blocks park in the cache
        # instead of the free list (tests/test_engine_prefix.py)
        params = _params()
        gen = np.random.default_rng(21)
        pa = gen.integers(0, 17, (12,)).astype(np.int32)  # 2 blocks
        pb = gen.integers(0, 17, (10,)).astype(np.int32)
        eng = _engine(params, batch_size=1, prefix_cache=False)
        ra = eng.submit(pa, 4)
        eng._admit_pending()
        # nothing is decoding, so the whole prompt prefills this tick:
        # both blocks of the padded-16 prompt get allocated
        eng._prefill_tick()
        used_a = set(eng._kinds[0].row_blocks[0])
        assert len(used_a) == 2
        comps = eng.run()
        assert [c.id for c in comps] == [ra]
        assert len(eng._kinds[0].free) == eng.usable_blocks  # all returned
        rb = eng.submit(pb, 4)
        eng._admit_pending()
        eng._prefill_tick()
        used_b = set(eng._kinds[0].row_blocks[0])
        assert used_b & used_a  # reuse, not fresh allocation
        eng.run()
        np.testing.assert_array_equal(
            eng.completions[rb].tokens, _reference(params, pb, 4)
        )

    def test_pool_gauges_track_occupancy(self):
        params = _params()
        gen = np.random.default_rng(23)
        eng = _engine(params, batch_size=1)
        eng.submit(gen.integers(0, 17, (5,)).astype(np.int32), 4)
        eng._admit_pending()
        eng._prefill_tick()
        m = obs.get_registry().metrics()["znicz_serve_kv_pool_blocks"]
        free = m.children()[("free",)].value
        used = m.children()[("used",)].value
        # gauges are last-setter-wins; this engine allocated last, so
        # they reflect ITS pool: one prompt block out
        assert used == len(eng._kinds[0].row_blocks[0]) == 1
        assert free == eng.usable_blocks - 1
        assert free + used == eng.usable_blocks
        eng.run()
        m = obs.get_registry().metrics()["znicz_serve_kv_pool_blocks"]
        assert m.children()[("used",)].value == 0

    def test_lazy_allocation_grows_with_decode(self):
        # a 5-token prompt (1 block) with a 20-token budget must NOT
        # reserve its worst case up front: blocks arrive as decode
        # crosses boundaries
        params = _params()
        gen = np.random.default_rng(25)
        p = gen.integers(0, 17, (5,)).astype(np.int32)
        eng = _engine(
            params, batch_size=1, eos_id=15, admit_every=4,
            prefix_cache=False,
        )
        eng.submit(p, 20)
        eng._admit_pending()
        eng._prefill_tick()
        n0 = len(eng._kinds[0].row_blocks[0])
        assert n0 == 1  # prompt block only — nothing reserved for decode
        eng._run_chunk()
        assert len(eng._kinds[0].row_blocks[0]) >= n0  # grew on demand
        eng.run()
        assert len(eng._kinds[0].free) == eng.usable_blocks


class TestPreemption:
    def test_pool_pressure_preempts_youngest_and_recomputes(self):
        # pool of 6 usable blocks; two full-budget requests need 5 + 4
        # blocks at peak -> the YOUNGER (second) must be preempted,
        # requeued, and still match its dense golden after recompute.
        # eos_id=15 is never greedily emitted by this seed's LM, so
        # both rows run their whole budget (verified by the reference).
        params = _params()
        gen = np.random.default_rng(7)
        pa = gen.integers(0, 17, (10,)).astype(np.int32)
        pb = gen.integers(0, 17, (5,)).astype(np.int32)
        ra = _reference(params, pa, 20, eos=15)
        rb = _reference(params, pb, 20, eos=15)
        assert len(ra) - len(pa) == 20 and len(rb) - len(pb) == 20
        before = _counter_value("znicz_serve_preemptions_total")
        admitted0 = _counter_value("znicz_serve_requests_admitted_total")
        ttft0 = _hist_count("znicz_serve_ttft_seconds")
        eng = _engine(params, eos_id=15, n_blocks=7)
        ia, ib = eng.submit(pa, 20), eng.submit(pb, 20)
        comps = eng.run()
        assert len(comps) == 2
        # ONE admission event per request, preemption-recompute aside:
        # readmission must not re-fire admitted/TTFT (PR-3 invariant:
        # admit events == requests)
        assert (
            _counter_value("znicz_serve_requests_admitted_total")
            - admitted0 == 2
        )
        assert _hist_count("znicz_serve_ttft_seconds") - ttft0 == 2
        np.testing.assert_array_equal(eng.completions[ia].tokens, ra)
        np.testing.assert_array_equal(eng.completions[ib].tokens, rb)
        st = eng.stats()
        assert st["preemptions"] >= 1
        after = _counter_value("znicz_serve_preemptions_total")
        assert after - before == st["preemptions"]
        # the pool is whole again
        assert st["pool_blocks_free"] == st["pool_blocks"]
        # the OLDER request was never preempted: it retired first
        assert comps[0].id == ia

    def test_single_request_never_self_deadlocks(self):
        # a request whose worst case equals the whole pool must run to
        # completion alone (validation guarantees it fits; preemption
        # must not evict the only occupant into a livelock)
        params = _params()
        gen = np.random.default_rng(29)
        p = gen.integers(0, 17, (10,)).astype(np.int32)  # padded 16
        # padded 16 + budget 24 = 40 tokens = 5 blocks = whole pool
        eng = _engine(params, batch_size=1, eos_id=15, n_blocks=6)
        rid = eng.submit(p, 24)
        eng.run()
        np.testing.assert_array_equal(
            eng.completions[rid].tokens, _reference(params, p, 24, eos=15)
        )
        assert eng.stats()["preemptions"] == 0


class TestPagedCompiles:
    """ISSUE 4 CI criterion: exactly one compile per paged program
    across a growth-past-one-block stream, cross-checked against
    compile_stats AND the znicz_serve_compiles_total registry counter;
    a second same-geometry engine adds ZERO."""

    def test_two_programs_cover_growth_past_one_block(self):
        params = _params()
        # unique geometry for this test (block_size 4, admit_every 5,
        # batch 3) so the process-wide first-compile ledger and jit
        # caches attribute deltas to THIS stream alone
        kw = dict(block_size=4, admit_every=5, batch_size=3, eos_id=15)
        structure = (True, 0, False)  # greedy, no top_k, no nucleus

        def stream(eng):
            # mixed lengths; budgets push every row well past its first
            # block (growth exercises lazy allocation + the chunk
            # program at several depths).  Fresh identical rng per call:
            # warm and cold streams are byte-identical, so the warm run
            # can reach no rung the cold one did not
            gen = np.random.default_rng(31)
            for n, b in ((3, 9), (6, 11), (10, 7), (5, 12)):
                eng.submit(
                    gen.integers(0, 17, (n,)).astype(np.int32), b
                )
            return eng.run()

        c0 = _compiles_total()
        eng = _engine(params, **kw)
        stream(eng)
        st = eng.compile_stats()
        # exactly ONE prefill program, every prompt length included,
        # plus decode-chunk variants keyed ONLY by the x2 window rung
        # (logarithmic in T_max/block_size — never per request shape)
        assert st["programs"][("prefill", 4, structure)] == 1
        chunk_keys = [
            k for k in st["programs"] if k[0] == "paged_chunk"
        ]
        assert chunk_keys and all(
            st["programs"][k] == 1 for k in chunk_keys
        )
        windows = sorted(k[3] for k in chunk_keys)
        assert len(set(windows)) == len(windows)  # one per rung
        assert all(w & (w - 1) == 0 for w in windows)  # powers of two
        assert st["n_programs"] == 1 + len(chunk_keys)
        c1 = _compiles_total()
        # registry agrees: every ledger entry was a true first compile
        assert c1 - c0 == st["n_programs"]
        n_pre = st["prefill_jit_entries"]
        n_chn = st["paged_chunk_jit_entries"]

        # warm path: a fresh same-geometry engine over a fresh stream
        # compiles NOTHING (jit caches untouched, registry delta zero)
        eng2 = _engine(params, **kw)
        stream(eng2)
        st2 = eng2.compile_stats()
        assert st2["prefill_jit_entries"] == n_pre
        assert st2["paged_chunk_jit_entries"] == n_chn
        assert _compiles_total() == c1
        assert st2["programs"] == st["programs"]
        assert st2["program_hits"] > 0

    def test_goldens_hold_across_the_growth_stream(self):
        params = _params()
        gen = np.random.default_rng(33)
        prompts = [
            gen.integers(0, 17, (n,)).astype(np.int32)
            for n in (3, 6, 10, 5)
        ]
        budgets = [9, 11, 7, 12]
        eng = _engine(
            params, block_size=4, admit_every=5, batch_size=3, eos_id=15
        )
        ids = [eng.submit(p, b) for p, b in zip(prompts, budgets)]
        eng.run()
        for p, b, rid in zip(prompts, budgets, ids):
            np.testing.assert_array_equal(
                eng.completions[rid].tokens,
                _reference(params, p, b, eos=15),
            )


class TestConcurrencyBeyondDense:
    def test_pool_packs_more_rows_than_the_dense_layout(self):
        # the ISSUE acceptance criterion: concurrent rows whose summed
        # DENSE demand exceeds the memory budget.  16 usable blocks x 8
        # = 128 cached tokens; a dense [n_slots, T_max=64] layout in
        # the same memory holds 2 slots — the paged engine decodes 4
        # rows at once (4 * 64 = 256 dense-tokens of demand)
        params = _params()
        gen = np.random.default_rng(35)
        prompts = [
            gen.integers(0, 17, (5,)).astype(np.int32) for _ in range(4)
        ]
        eng = _engine(
            params, batch_size=4, n_blocks=17, eos_id=15, admit_every=2
        )
        ids = [eng.submit(p, 9) for p in prompts]
        eng.run()
        for p, rid in zip(prompts, ids):
            np.testing.assert_array_equal(
                eng.completions[rid].tokens,
                _reference(params, p, 9, eos=15),
            )
        st = eng.stats()
        dense_slots_same_memory = (st["pool_blocks"] * BS) // T_MAX
        assert dense_slots_same_memory == 2
        assert st["peak_active"] == 4
        assert st["peak_active"] * T_MAX > st["pool_blocks"] * BS
        assert st["preemptions"] == 0  # fits — pressure never triggered


class TestPagedValidation:
    def test_submit_names_the_paged_backend(self):
        params = _params()
        eng = _engine(params, n_blocks=5)  # 4 usable blocks = 32 tokens
        with pytest.raises(ValueError, match="empty"):
            eng.submit(np.asarray([], np.int32), 4)
        with pytest.raises(ValueError, match="max_new_tokens"):
            eng.submit(np.asarray([1, 2], np.int32), 0)
        with pytest.raises(ValueError, match="paged KV pool"):
            eng.submit(np.arange(5, dtype=np.int32), 30)  # 8+30 -> 5 blk
        with pytest.raises(ValueError, match="positional window"):
            eng.submit(np.arange(5, dtype=np.int32), 60)  # 8+60 > t_max

    def test_default_pool_runs_out_of_positions_first(self):
        # the default pool holds a full window a slot, so what a request
        # can outgrow there is the positional window, and the error
        # says so
        params = _params()
        eng = PagedDecodeEngine(
            params, n_heads=HEADS, eos_id=EOS, batch_size=2
        )
        assert eng.usable_blocks == 2 * (T_MAX // 16)
        with pytest.raises(ValueError, match="empty"):
            eng.submit(np.asarray([], np.int32), 4)
        with pytest.raises(ValueError, match="max_new_tokens"):
            eng.submit(np.asarray([1, 2], np.int32), 0)
        with pytest.raises(ValueError, match="positional window"):
            eng.submit(np.arange(5, dtype=np.int32), 60)  # 16 + 60 > 64
        with pytest.raises(ValueError, match="eos_id"):
            PagedDecodeEngine(params, n_heads=HEADS, eos_id=99)

    def test_constructor_validation(self):
        params = _params()
        with pytest.raises(ValueError, match="block_size"):
            _engine(params, block_size=0)
        with pytest.raises(ValueError, match="n_blocks"):
            _engine(params, n_blocks=1)
