"""Speculative decoding in the paged engine: goldens, rollback, leaks.

ISSUE 12 acceptance: greedy speculative decode must be TOKEN-IDENTICAL
to non-speculative decode (same engine, spec off) — through mixed
prompt lengths, chunked prefill, preemption-with-rollback and the
prefix cache — because verification scores the drafted tokens with
exactly the decode path's math and keeps only the longest agreeing
prefix.  Rejected drafts roll back by TRUNCATING the block table
(refcounts reclaim the blocks — the leak sweep must come back clean),
and the bucketed verify ladder must add ZERO compiled programs per
accepted length (pinned against the engine ledger, the jit caches AND
``znicz_serve_compiles_total``).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from znicz_tpu import observability as obs
from znicz_tpu.core import prng
from znicz_tpu.services.engine import PagedDecodeEngine
from znicz_tpu.services.errors import SpeculationUnsupportedError
from znicz_tpu.workflow import generate as G
from znicz_tpu.workflow.generate import PromptLookupDrafter
from znicz_tpu.workflow.transformer import init_lm_params

EOS = 15  # never greedily emitted by this seed's LM at small budgets
HEADS = 4
T_MAX = 96
BS = 8


def _params(seed=27, max_seq=T_MAX):
    prng.seed_all(seed)
    return init_lm_params(17, 32, 2, HEADS, max_seq=max_seq)


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


def _engine(params, **kw):
    kw.setdefault("n_heads", HEADS)
    kw.setdefault("eos_id", EOS)
    kw.setdefault("batch_size", 2)
    kw.setdefault("block_size", BS)
    kw.setdefault("max_seq", T_MAX)
    kw.setdefault("admit_every", 4)
    kw.setdefault("spec_k", 7)
    return PagedDecodeEngine(params, **kw)


def _tokens(rng, n):
    return rng.integers(1, 17, (n,)).astype(np.int32)


def _compiles_total():
    m = obs.get_registry().metrics().get("znicz_serve_compiles_total")
    if m is None:
        return 0.0
    return sum(c.value for c in m.children().values())


def _assert_no_leaks(eng):
    assert eng.active == 0 and eng.prefilling == 0 and eng.pending == 0
    eng.flush_prefix_cache()
    assert len(eng._kinds[0].lru) == 0
    assert sorted(eng._kinds[0].free) == list(range(1, eng.n_blocks))
    assert (eng._kinds[0].ref == 0).all()


class OracleDrafter:
    """Test drafter with perfect foresight: proposes the REFERENCE
    continuation of whatever context it is shown, so every draft is
    accepted — the deterministic way to exercise the accept path.
    ``sizes`` cycles the per-call draft length (None = always k)."""

    def __init__(self, refs, sizes=None):
        self.refs = [np.asarray(r, np.int32) for r in refs]
        self.sizes = list(sizes) if sizes else None
        self._call = 0

    def propose(self, context, k: int) -> np.ndarray:
        ctx = np.asarray(context, np.int32)
        if self.sizes:
            k = min(k, self.sizes[self._call % len(self.sizes)])
            self._call += 1
        for ref in self.refs:
            if ctx.size < ref.size and np.array_equal(
                ref[: ctx.size], ctx
            ):
                return ref[ctx.size: ctx.size + k].copy()
        return np.zeros((0,), np.int32)


class JunkDrafter:
    """Always proposes the same (almost always wrong) tokens — the
    deterministic way to exercise full rollback every verify."""

    def __init__(self, token=1):
        self.token = token

    def propose(self, context, k: int) -> np.ndarray:
        return np.full((k,), self.token, np.int32)


class TestGreedyGoldens:
    def test_mixed_lengths_golden_vs_nonspec(self):
        # mixed prompt lengths (several chunked-prefill shapes) with
        # the REAL prompt-lookup drafter: spec engine == spec-off
        # engine == per-request generate(), token for token
        params = _params()
        rng = np.random.default_rng(5)
        prompts = [_tokens(rng, n) for n in (5, 12, 20, 9, 17, 33)]
        prompts.append(np.tile(np.array([3, 5, 7, 2], np.int32), 8))
        engines = {
            "off": _engine(params, spec_k=0),
            "spec": _engine(
                params, drafter=PromptLookupDrafter(3, 1)
            ),
        }
        ids = {
            name: [eng.submit(p, 24) for p in prompts]
            for name, eng in engines.items()
        }
        for eng in engines.values():
            eng.run()
        for i, p in enumerate(prompts):
            ref = _reference(params, p, 24)
            for name, eng in engines.items():
                got = eng.completions[ids[name][i]].tokens
                assert np.array_equal(got, ref), (name, i)
        assert engines["spec"].spec_stats()["verify_steps"] > 0
        _assert_no_leaks(engines["spec"])

    def test_oracle_drafter_accepts_everything(self):
        # perfect drafts: acceptance rate 1.0, and the whole budget
        # arrives in a handful of verify steps
        params = _params()
        rng = np.random.default_rng(5)
        p = _tokens(rng, 10)
        ref = _reference(params, p, 20)
        assert ref.size == p.size + 20  # long run: drafting has work
        eng = _engine(params, drafter=OracleDrafter([ref]))
        rid = eng.submit(p, 20)
        eng.run()
        comp = eng.completions[rid]
        assert np.array_equal(comp.tokens, ref)
        sp = eng.spec_stats()
        assert sp["enabled"] and sp["drafted"] > 0
        assert sp["accepted"] == sp["drafted"]
        assert sp["rejected"] == 0
        assert sp["acceptance_rate"] == 1.0
        # far fewer verify steps than emitted tokens
        assert sp["verify_steps"] < comp.n_new
        # the per-request breakdown carries the same tallies
        assert comp.timings["spec_drafted"] == sp["drafted"]
        assert comp.timings["spec_accepted"] == sp["accepted"]
        _assert_no_leaks(eng)

    def test_junk_drafter_rolls_everything_back(self):
        # every draft rejected: still golden (the bonus token IS the
        # greedy token), every rejected block reclaimed
        params = _params()
        rng = np.random.default_rng(13)
        prompts = [_tokens(rng, n) for n in (6, 14)]
        eng = _engine(params, drafter=JunkDrafter(token=2))
        ids = [eng.submit(p, 16) for p in prompts]
        eng.run()
        for rid, p in zip(ids, prompts):
            assert np.array_equal(
                eng.completions[rid].tokens, _reference(params, p, 16)
            )
        sp = eng.spec_stats()
        assert sp["drafted"] > 0
        # the constant junk token may collide with the true greedy
        # token occasionally; rejection must dominate
        assert sp["rejected"] > sp["accepted"]
        _assert_no_leaks(eng)

    def test_eos_inside_accepted_prefix_retires_exactly(self):
        # a draft that includes the true EOS retires the row AT the
        # EOS, not past it — same contract as the chunk collection loop
        params = _params()
        rng = np.random.default_rng(17)
        for n in (4, 7, 11, 19, 26):
            p = _tokens(rng, n)
            ref = _reference(params, p, 40)
            eng = _engine(params, drafter=OracleDrafter([ref]))
            rid = eng.submit(p, 40)
            eng.run()
            comp = eng.completions[rid]
            assert np.array_equal(comp.tokens, ref)
            if ref[-1] == EOS:
                assert comp.finish_reason == "eos"
            else:
                assert comp.finish_reason == "budget"
            _assert_no_leaks(eng)


class TestRollback:
    def test_rollback_truncates_the_block_table(self):
        # white-box: a junk verify allocates blocks for the full
        # bucketed width, then rollback shrinks the row back to the
        # accepted prefix — tables and row_blocks agree, and the freed
        # blocks are allocatable again
        params = _params()
        rng = np.random.default_rng(5)
        p = _tokens(rng, BS - 1)  # one block of prompt, 30-token run
        # cache OFF: released blocks must come back to the FREE list
        # (cache-on parks published blocks in the LRU instead)
        eng = _engine(
            params, batch_size=1, drafter=JunkDrafter(),
            prefix_cache=False,
        )
        rid = eng.submit(p, 30)
        # drive tick by tick so we can observe mid-stream state
        free0 = len(eng._kinds[0].free)
        while eng._has_work():
            eng._admit_pending()
            eng._prefill_tick()
            if eng.active:
                eng._run_chunk()
            row = eng._kinds[0].row_blocks[0]
            # invariant after every tick: the table NEVER keeps blocks
            # past the valid-KV prefix + 0 or 1 in-progress block
            if eng._slots[0] is not None and eng._slots[0]["mode"] == "decode":
                keep = (int(eng._pos[0]) - 1) // BS + 1
                assert len(row) == keep
                assert all(
                    int(eng._kinds[0].tables[0, j]) == row[j]
                    for j in range(len(row))
                )
        assert np.array_equal(
            eng.completions[rid].tokens, _reference(params, p, 30)
        )
        assert len(eng._kinds[0].free) == free0
        _assert_no_leaks(eng)

    def test_preemption_under_spec_pressure_stays_golden(self):
        # a pool too small for everyone + spec verify allocating ahead:
        # preemption (publish + release + requeue + recompute) must
        # interleave with speculative rollback without corrupting anyone
        params = _params()
        rng = np.random.default_rng(23)
        prompts = [_tokens(rng, n) for n in (2 * BS, 2 * BS + 3, BS + 1)]
        eng = _engine(
            params, batch_size=3, n_blocks=10,
            drafter=PromptLookupDrafter(3, 1),
        )
        ids = [eng.submit(p, 24) for p in prompts]
        eng.run()
        for rid, p in zip(ids, prompts):
            assert np.array_equal(
                eng.completions[rid].tokens, _reference(params, p, 24)
            )
        _assert_no_leaks(eng)

    def test_forced_preemption_with_oracle_drafts(self):
        # oracle drafts make every verify allocate the full width, so
        # a tight pool MUST preempt; survivors and victims both golden
        params = _params()
        rng = np.random.default_rng(29)
        prompts = [_tokens(rng, n) for n in (BS, BS + 2, BS - 1)]
        refs = [_reference(params, p, 30) for p in prompts]
        eng = _engine(
            params, batch_size=3, n_blocks=9,
            drafter=OracleDrafter(refs),
        )
        ids = [eng.submit(p, 30) for p in prompts]
        eng.run()
        for rid, ref in zip(ids, refs):
            assert np.array_equal(eng.completions[rid].tokens, ref)
        _assert_no_leaks(eng)


class TestPrefixCacheInteraction:
    def test_spec_decode_fills_publishable_blocks(self):
        # multi-turn: turn 1 decodes speculatively; turn 2's prompt
        # extends turn 1's full output and must map the blocks spec
        # decode filled — cached_tokens > 0 AND both turns golden
        params = _params()
        rng = np.random.default_rng(31)
        p1 = _tokens(rng, BS)
        ref1 = _reference(params, p1, 18)
        eng = _engine(params, drafter=OracleDrafter([ref1]))
        r1 = eng.submit(p1, 18)
        eng.run()
        assert np.array_equal(eng.completions[r1].tokens, ref1)
        p2 = np.concatenate([ref1, _tokens(rng, 3)])
        ref2 = _reference(params, p2, 12)
        eng.drafter = OracleDrafter([ref2])
        r2 = eng.submit(p2, 12)
        eng.run()
        assert np.array_equal(eng.completions[r2].tokens, ref2)
        st = eng.stats()
        assert st["prefix_cache"]["hits"] > 0
        assert eng.completions[r2].timings["cached_tokens"] > 0
        _assert_no_leaks(eng)

    def test_shared_prefix_admission_then_spec_golden(self):
        # two requests sharing a long prefix, spec on: the second maps
        # cached blocks, then speculates on top of them
        params = _params()
        rng = np.random.default_rng(37)
        s = _tokens(rng, 2 * BS)
        eng = _engine(params, drafter=PromptLookupDrafter(3, 1))
        pa = np.concatenate([s, _tokens(rng, 5)])
        pb = np.concatenate([s, _tokens(rng, 7)])
        ra = eng.submit(pa, 10)
        eng.run()
        rb = eng.submit(pb, 10)
        eng.run()
        assert np.array_equal(
            eng.completions[ra].tokens, _reference(params, pa, 10)
        )
        assert np.array_equal(
            eng.completions[rb].tokens, _reference(params, pb, 10)
        )
        assert eng.stats()["prefix_cache"]["hits"] >= 2
        _assert_no_leaks(eng)


class TestZeroNewPrograms:
    def test_verify_ladder_and_accepted_lengths_compile_nothing_new(self):
        # drive every verify bucket (draft sizes 1/3/7 -> widths 2/4/8)
        # on a warm engine: the ledger, the jit caches and the registry
        # counter must agree, and a SECOND engine with the same
        # geometry — replaying varied accepted lengths — adds ZERO
        params = _params()
        rng = np.random.default_rng(4)

        def build():
            p = _tokens(rng, 6)
            ref = _reference(params, p, 26)
            assert ref.size == p.size + 26  # full-budget run
            eng = _engine(
                params, batch_size=1,
                drafter=OracleDrafter([ref], sizes=(1, 3, 7)),
            )
            return eng, p, ref

        eng, p, ref = build()
        rid = eng.submit(p, 26)
        eng.run()
        assert np.array_equal(eng.completions[rid].tokens, ref)
        st0 = eng.compile_stats()
        widths = {
            key[1] for key in st0["programs"] if key[0] == "spec_verify"
        }
        assert widths == {2, 4, 8}
        c0 = _compiles_total()
        # second same-geometry engine: different prompt, different
        # accepted lengths, same bucket ladder -> all cache hits
        eng2, p2, ref2 = build()
        rid2 = eng2.submit(p2, 26)
        eng2.run()
        assert np.array_equal(eng2.completions[rid2].tokens, ref2)
        st1 = eng2.compile_stats()
        assert set(st1["programs"]) <= set(st0["programs"])
        assert (
            st1["spec_verify_jit_entries"]
            == st0["spec_verify_jit_entries"]
        )
        assert st1["prefill_jit_entries"] == st0["prefill_jit_entries"]
        assert (
            st1["paged_chunk_jit_entries"]
            == st0["paged_chunk_jit_entries"]
        )
        assert _compiles_total() == c0
        _assert_no_leaks(eng)
        _assert_no_leaks(eng2)

    def test_spec_off_engine_never_touches_verify_program(self):
        params = _params()
        rng = np.random.default_rng(43)
        eng = _engine(params, spec_k=0)
        eng.submit(_tokens(rng, 9), 8)
        eng.run()
        assert not any(
            key[0] == "spec_verify" for key in eng.compile_stats()["programs"]
        )
        assert eng.spec_stats() == {
            "enabled": False,
            "k": 0,
            "buckets": list(G.DEFAULT_SPEC_BUCKETS),
            "drafted": 0,
            "accepted": 0,
            "rejected": 0,
            "verify_steps": 0,
            "acceptance_rate": 0.0,
        }


class TestSampledSpec:
    def test_sampled_path_completes_in_vocab(self):
        # temperature > 0: distribution-level correctness (standard
        # rejection against the point-mass draft) is not goldenable
        # token-wise; pin what is checkable — typed completions, tokens
        # in vocab, spec accounting consistent, no leaks
        params = _params()
        rng = np.random.default_rng(47)
        eng = _engine(
            params, spec_k=3, temperature=0.8, top_k=5,
            rng=jax.random.key(3), drafter=PromptLookupDrafter(3, 1),
        )
        ids = [eng.submit(_tokens(rng, n), 12) for n in (5, 9, 14, 21)]
        eng.run()
        for rid in ids:
            comp = eng.completions[rid]
            assert comp.finish_reason in ("eos", "budget")
            assert (comp.tokens >= 0).all() and (comp.tokens < 17).all()
        sp = eng.spec_stats()
        assert sp["drafted"] == sp["accepted"] + sp["rejected"]
        _assert_no_leaks(eng)


class TestSpecConfig:
    def test_stats_carry_a_disabled_spec_subdict_with_speculation_off(self):
        # callers read ONE shape whether or not the engine speculates
        params = _params()
        eng = _engine(params, spec_k=0)
        sp = eng.stats()["spec"]
        assert sp["enabled"] is False and sp["k"] == 0
        assert sp["drafted"] == sp["accepted"] == sp["verify_steps"] == 0

    def test_paged_validates_spec_args(self):
        params = _params()
        with pytest.raises(ValueError, match="spec_k"):
            _engine(params, spec_k=-1)
        with pytest.raises(ValueError, match="spec_buckets"):
            _engine(params, spec_buckets=(1, 4))
        with pytest.raises(ValueError, match="spec_buckets"):
            _engine(params, spec_buckets=(4, 2))
        # a drafter with speculation OFF is a config trap, not a no-op
        with pytest.raises(ValueError, match="spec_k"):
            _engine(params, spec_k=0, drafter=PromptLookupDrafter())
        eng = _engine(params, spec_k=0)
        assert eng.drafter is None

    def test_spec_stats_in_paged_report(self):
        params = _params()
        eng = _engine(params, spec_k=3)
        sp = eng.stats()["spec"]
        assert sp["enabled"] and sp["k"] == 3
        assert sp["buckets"] == list(G.DEFAULT_SPEC_BUCKETS)


class TestPromptLookupDrafter:
    def test_most_recent_match_wins(self):
        d = PromptLookupDrafter(ngram_max=2, ngram_min=2)
        #        [1 2] -> 3 ... [1 2] -> 4 ...   query tail [1 2]
        ctx = [1, 2, 3, 9, 1, 2, 4, 9, 1, 2]
        assert d.propose(ctx, 1).tolist() == [4]

    def test_periodic_run_drafts_full_k(self):
        # inside a long run the latest occurrence with k continuation
        # tokens is preferred — a period-1 run drafts k tokens, not 1
        d = PromptLookupDrafter()
        ctx = [9, 4] + [7] * 10
        assert d.propose(ctx, 4).tolist() == [7, 7, 7, 7]

    def test_k_clamp_and_no_match(self):
        d = PromptLookupDrafter()
        assert d.propose([1, 2, 3, 4], 4).size == 0  # no repeat
        assert d.propose([5, 6, 7], 0).size == 0  # k=0
        # short tail continuation clamps below k
        assert d.propose([1, 2, 8, 1, 2], 5).tolist() == [8, 1, 2]

    def test_longer_ngram_preferred(self):
        d = PromptLookupDrafter(ngram_max=3, ngram_min=1)
        # 1-gram [2] would match index 1 (-> 9); the 3-gram match is
        # the truthier continuation and must win
        ctx = [1, 2, 9, 3, 1, 2, 5, 8, 3, 1, 2]
        assert d.propose(ctx, 1).tolist() == [5]

    def test_validation(self):
        with pytest.raises(ValueError):
            PromptLookupDrafter(ngram_max=0)
        with pytest.raises(ValueError):
            PromptLookupDrafter(ngram_max=2, ngram_min=3)


class TestObservability:
    def test_counters_and_histogram_advance(self):
        params = _params()
        rng = np.random.default_rng(53)
        reg = obs.get_registry().metrics()

        def val(name):
            m = obs.get_registry().metrics().get(name)
            return sum(c.value for c in m.children().values()) if m else 0.0

        d0 = val("znicz_serve_spec_drafted_total")
        a0 = val("znicz_serve_spec_accepted_total")
        r0 = val("znicz_serve_spec_rejected_total")
        h = obs.get_registry().metrics().get(
            "znicz_serve_spec_accept_length"
        )
        h0 = sum(c.count for c in h.children().values()) if h else 0
        p = _tokens(rng, 10)
        ref = _reference(params, p, 16)
        eng = _engine(params, drafter=OracleDrafter([ref]))
        eng.submit(p, 16)
        eng.run()
        sp = eng.spec_stats()
        assert val("znicz_serve_spec_drafted_total") - d0 == sp["drafted"]
        assert val("znicz_serve_spec_accepted_total") - a0 == sp["accepted"]
        assert val("znicz_serve_spec_rejected_total") - r0 == sp["rejected"]
        h = obs.get_registry().metrics()["znicz_serve_spec_accept_length"]
        h1 = sum(c.count for c in h.children().values())
        assert h1 - h0 == sp["verify_steps"]
        assert reg is not None  # registry untouched shape-wise
