"""Prefix-cache serving: goldens, COW, eviction, preemption, leaks.

ISSUE 5 acceptance: the prefix-cached paged engine must stay a
TRANSPARENT batching layer — every completion golden-matches the
single-request ``generate()`` output — through cross-request prefix
sharing, copy-on-write divergence, LRU eviction under pool pressure,
and preemption of requests holding SHARED blocks (refcounts must keep
survivors' blocks alive).  And prefix reuse must add ZERO compiled
programs: it only skips iterations of the existing chunk program.
"""

import jax.numpy as jnp
import numpy as np

from znicz_tpu import observability as obs
from znicz_tpu.core import prng
from znicz_tpu.services.engine import PagedDecodeEngine
from znicz_tpu.workflow import generate as G
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
    return PagedDecodeEngine(params, **kw)


def _counter_value(name):
    m = obs.get_registry().metrics().get(name)
    return 0.0 if m is None else m.value


def _compiles_total():
    """Registry sum of the labeled znicz_serve_compiles_total family."""
    m = obs.get_registry().metrics().get("znicz_serve_compiles_total")
    if m is None:
        return 0.0
    return sum(c.value for c in m.children().values())


def _assert_no_leaks(eng):
    """Refcount-leak sweep: after every request retires and the cache
    is flushed, the free list holds the whole pool minus the reserved
    null block, and no refcount is outstanding."""
    assert eng.active == 0 and eng.prefilling == 0 and eng.pending == 0
    eng.flush_prefix_cache()
    assert len(eng._kinds[0].cache) == 0 and len(eng._kinds[0].block_hash) == 0
    assert len(eng._kinds[0].lru) == 0
    assert sorted(eng._kinds[0].free) == list(range(1, eng.n_blocks))
    assert (eng._kinds[0].ref == 0).all()


def _tokens(rng, n):
    return rng.integers(0, 17, (n,)).astype(np.int32)


class TestSharedPrefix:
    def test_two_requests_share_a_long_prefix(self):
        # (a) S is 2 full blocks; A = S + 5, B = S + 7 different tokens.
        # After A retires, B's admission must map S's blocks from the
        # cache and chunk-prefill ONLY the tail: prefill_chunks ==
        # ceil(tail / block_size), zero chunks for the shared part.
        params = _params()
        rng = np.random.default_rng(41)
        s = _tokens(rng, 2 * BS)
        pa = np.concatenate([s, _tokens(rng, 5)])
        pb = np.concatenate([s, _tokens(rng, 7)])
        eng = _engine(params)
        ra = eng.submit(pa, 6)
        eng.run()
        np.testing.assert_array_equal(
            eng.completions[ra].tokens, _reference(params, pa, 6)
        )
        hits0 = _counter_value("znicz_serve_prefix_hits_total")
        toks0 = _counter_value("znicz_serve_prefix_cached_tokens_total")
        chunks0 = _counter_value("znicz_serve_prefill_chunks_total")
        rb = eng.submit(pb, 6)
        eng.run()
        np.testing.assert_array_equal(
            eng.completions[rb].tokens, _reference(params, pb, 6)
        )
        # B: 23 tokens = 2 cached blocks + a 7-token tail -> ONE chunk
        assert (
            _counter_value("znicz_serve_prefill_chunks_total") - chunks0
            == 1
        )
        assert _counter_value("znicz_serve_prefix_hits_total") - hits0 == 2
        assert (
            _counter_value("znicz_serve_prefix_cached_tokens_total")
            - toks0
            == 2 * BS
        )
        st = eng.stats()["prefix_cache"]
        assert st["enabled"] and st["hits"] >= 2
        assert st["cached_tokens"] >= 2 * BS
        _assert_no_leaks(eng)

    def test_multi_turn_reuses_generated_blocks(self):
        # the cache covers GENERATED positions too: turn 2's prompt is
        # turn 1's full output, so its cached chain extends past turn
        # 1's prompt into blocks decode filled
        params = _params()
        rng = np.random.default_rng(43)
        p1 = _tokens(rng, 11)
        eng = _engine(params)
        r1 = eng.submit(p1, 8)
        eng.run()
        out1 = eng.completions[r1].tokens
        p2 = np.concatenate([out1, _tokens(rng, 4)])
        hits0 = _counter_value("znicz_serve_prefix_hits_total")
        r2 = eng.submit(p2, 5)
        eng.run()
        np.testing.assert_array_equal(
            eng.completions[r2].tokens, _reference(params, p2, 5)
        )
        # out1 is 11 + ~8 tokens: at least the first 2 blocks (16
        # positions, the last of them decode-written) must have hit
        assert _counter_value("znicz_serve_prefix_hits_total") - hits0 >= 2
        _assert_no_leaks(eng)

    def test_prefix_hits_do_not_consume_allocation(self):
        # a hit maps resident blocks: submitting B after A must
        # allocate only B's tail blocks (white-box: pool accounting)
        params = _params()
        rng = np.random.default_rng(45)
        s = _tokens(rng, 2 * BS)
        eng = _engine(params, batch_size=1)
        eng.submit(np.concatenate([s, _tokens(rng, 3)]), 4)
        eng.run()
        cached = len(eng._kinds[0].lru)
        assert cached >= 2  # S's blocks are cache-only now
        eng.submit(np.concatenate([s, _tokens(rng, 6)]), 4)
        eng._admit_pending()
        row = eng._kinds[0].row_blocks[0]
        assert len(row) == 2  # mapped, not allocated: tail not yet run
        assert all(eng._kinds[0].ref[b] == 1 for b in row)
        eng.run()
        _assert_no_leaks(eng)


class TestCopyOnWrite:
    def test_fully_cached_prompt_cow_reruns_final_block(self):
        # (b) an identical block-aligned prompt resubmitted: every block
        # hits, but the first token needs logits, so the final block's
        # chunk re-runs after a COW split — the CACHED block must stay
        # pristine (a third submission hits it again), and the output
        # must golden-match
        params = _params()
        rng = np.random.default_rng(47)
        p = _tokens(rng, 2 * BS)  # exactly 2 blocks, aligned
        ref = _reference(params, p, 6)
        eng = _engine(params)
        r1 = eng.submit(p, 6)
        eng.run()
        np.testing.assert_array_equal(eng.completions[r1].tokens, ref)
        chunks0 = _counter_value("znicz_serve_prefill_chunks_total")
        r2 = eng.submit(p, 6)
        eng.run()
        np.testing.assert_array_equal(eng.completions[r2].tokens, ref)
        st = eng.stats()["prefix_cache"]
        assert st["cow_splits"] >= 1
        # only the re-run chunk executed (1 of 2 blocks)
        assert (
            _counter_value("znicz_serve_prefill_chunks_total") - chunks0
            == 1
        )
        # the COW preserved the cache: a third run hits both blocks again
        hits0 = _counter_value("znicz_serve_prefix_hits_total")
        r3 = eng.submit(p, 6)
        eng.run()
        np.testing.assert_array_equal(eng.completions[r3].tokens, ref)
        assert _counter_value("znicz_serve_prefix_hits_total") - hits0 == 2
        _assert_no_leaks(eng)

    def test_divergence_mid_block_misses_from_that_block_on(self):
        # (b) divergence MID-block: B shares only A's first block-and-a-
        # half of tokens; the chain must hit block 0 and miss block 1,
        # and both outputs golden-match
        params = _params()
        rng = np.random.default_rng(49)
        pa = _tokens(rng, 2 * BS + 3)
        pb = pa.copy()[: 2 * BS]
        pb[BS + 4] = (pb[BS + 4] + 1) % 17  # diverge inside block 1
        eng = _engine(params)
        ra = eng.submit(pa, 5)
        eng.run()
        hits0 = _counter_value("znicz_serve_prefix_hits_total")
        miss0 = _counter_value("znicz_serve_prefix_misses_total")
        rb = eng.submit(pb, 5)
        eng.run()
        np.testing.assert_array_equal(
            eng.completions[ra].tokens, _reference(params, pa, 5)
        )
        np.testing.assert_array_equal(
            eng.completions[rb].tokens, _reference(params, pb, 5)
        )
        assert _counter_value("znicz_serve_prefix_hits_total") - hits0 == 1
        assert (
            _counter_value("znicz_serve_prefix_misses_total") - miss0 == 1
        )
        _assert_no_leaks(eng)

    def test_decode_write_guard_copies_shared_content(self):
        # white-box: force the decode write-guard's COPYING split by
        # caching the row's tail block mid-flight (as an eager publish-
        # on-fill policy would).  The copy must preserve the prompt's
        # K/V — the golden catches a miscopy — and the original block's
        # content stays cached
        params = _params()
        rng = np.random.default_rng(51)
        p = _tokens(rng, 5)
        eng = _engine(params, batch_size=1)
        eng.submit(p, 8)
        eng._admit_pending()
        eng._prefill_tick()  # admitted: block 0 holds the prompt K/V
        blk = int(eng._kinds[0].row_blocks[0][0])
        eng._kinds[0].cache[b"eager-fill"] = blk
        eng._kinds[0].block_hash[blk] = b"eager-fill"
        eng.run()
        comp = next(iter(eng.completions.values()))
        np.testing.assert_array_equal(
            comp.tokens, _reference(params, p, 8)
        )
        st = eng.stats()
        assert st["prefix_cache"]["cow_splits"] >= 1
        assert ("cow", BS) in st["programs"]
        _assert_no_leaks(eng)


class TestEvictionUnderPressure:
    def test_cache_evicts_before_preemption_and_readmits(self):
        # (c) a pool too small for two cached prefixes: the second
        # stream must EVICT cache (never preempt — nobody is live), and
        # re-admitting the evicted prefix recomputes and still matches
        params = _params()
        rng = np.random.default_rng(53)
        pa = _tokens(rng, 2 * BS)
        pb = _tokens(rng, 2 * BS)
        # 4 usable blocks: one 16-token prompt + budget 8 peaks at 3,
        # leaving too little to keep both retired prefixes cached
        eng = _engine(params, batch_size=1, n_blocks=5)
        ra = eng.submit(pa, 8)
        eng.run()
        ev0 = _counter_value("znicz_serve_prefix_evictions_total")
        rb = eng.submit(pb, 8)
        eng.run()
        assert (
            _counter_value("znicz_serve_prefix_evictions_total") - ev0
            >= 1
        )
        assert eng.stats()["preemptions"] == 0
        # pa's chain was (at least partly) evicted; resubmit: recompute
        # whatever is gone, goldens regardless
        r2 = eng.submit(pa, 8)
        eng.run()
        np.testing.assert_array_equal(
            eng.completions[ra].tokens, eng.completions[r2].tokens
        )
        np.testing.assert_array_equal(
            eng.completions[r2].tokens, _reference(params, pa, 8)
        )
        np.testing.assert_array_equal(
            eng.completions[rb].tokens, _reference(params, pb, 8)
        )
        _assert_no_leaks(eng)


class TestPreemptionWithSharedBlocks:
    def test_survivor_keeps_shared_blocks_through_preemption(self):
        # (d) A and B both map S's cached blocks; pool pressure preempts
        # the younger B — the refcounts must keep S's blocks alive for
        # A, and BOTH outputs still golden-match after B's recompute
        # seed 59: neither request greedily emits EOS inside its
        # 12-token budget (verified against the reference), so decode
        # growth genuinely reaches peak block demand
        params = _params()
        rng = np.random.default_rng(59)
        s = _tokens(rng, 2 * BS)
        pa = np.concatenate([s, _tokens(rng, 3)])
        pb = np.concatenate([s, _tokens(rng, 6)])
        # pool: 6 usable.  Seeding S caches 2 blocks; A and B map them
        # (shared, ref 2) and need 2 + 3 private blocks at peak — one
        # more than the 4 the free list holds, and the only cached
        # blocks are the CLAIMED (unevictable) shared pair, so the
        # youngest (B) must be preempted, readmitted after A retires,
        # and recompute — with A's output untouched because refcounts
        # kept the shared pair alive through B's release
        eng = _engine(params, n_blocks=7)
        r0 = eng.submit(s, 1)
        eng.run()
        pre0 = eng.stats()["preemptions"]
        ia, ib = eng.submit(pa, 12), eng.submit(pb, 12)
        eng.run()
        st = eng.stats()
        assert st["preemptions"] - pre0 >= 1
        np.testing.assert_array_equal(
            eng.completions[ia].tokens, _reference(params, pa, 12)
        )
        np.testing.assert_array_equal(
            eng.completions[ib].tokens, _reference(params, pb, 12)
        )
        assert eng.completions[r0] is not None
        _assert_no_leaks(eng)


class TestZeroNewPrograms:
    def test_prefix_reuse_compiles_nothing(self):
        # (e) after a cold request warms the ONE prefill program and the
        # decode-window rung, a prefix-sharing request adds ZERO
        # compiled programs: reuse only SKIPS iterations of the existing
        # chunk program.  Cross-checked against the engine ledger, the
        # process-wide jit caches AND the registry compile counter.
        params = _params()
        rng = np.random.default_rng(59)
        s = _tokens(rng, 2 * BS)
        eng = _engine(params)
        # cold: 21-token prompt, budget 6 -> window rung 4 blocks
        ra = eng.submit(np.concatenate([s, _tokens(rng, 5)]), 6)
        eng.run()
        st0 = eng.compile_stats()
        c0 = _compiles_total()
        # warm: shares S, same window rung, cache hits > 0
        rb = eng.submit(np.concatenate([s, _tokens(rng, 7)]), 6)
        eng.run()
        st1 = eng.compile_stats()
        assert eng.stats()["prefix_cache"]["hits"] >= 2
        assert st1["programs"] == st0["programs"]
        assert st1["prefill_jit_entries"] == st0["prefill_jit_entries"]
        assert (
            st1["paged_chunk_jit_entries"]
            == st0["paged_chunk_jit_entries"]
        )
        assert st1["cow_jit_entries"] == st0["cow_jit_entries"]
        assert _compiles_total() == c0
        for rid in (ra, rb):
            assert eng.completions[rid].n_new >= 1
        _assert_no_leaks(eng)


class TestLeakSweep:
    def test_mixed_stream_leaves_no_dangling_refcounts(self):
        # (f) sharing + COW + eviction + preemption in one stream, then
        # the sweep: free-list == pool minus the null block, refs all 0
        params = _params()
        rng = np.random.default_rng(61)
        s = _tokens(rng, 2 * BS)
        eng = _engine(params, n_blocks=9)
        eng.submit(s, 1)
        eng.run()
        ids = [
            eng.submit(np.concatenate([s, _tokens(rng, k)]), 10)
            for k in (3, 6, 4)
        ]
        eng.submit(s, 6)  # fully-cached resubmit: COW re-run
        eng.run()
        for rid in ids:
            assert eng.completions[rid].finish_reason in ("eos", "budget")
        _assert_no_leaks(eng)
        # flushing again is idempotent
        assert eng.flush_prefix_cache() == 0

    def test_disabled_cache_keeps_plain_free_list(self):
        params = _params()
        rng = np.random.default_rng(63)
        eng = _engine(params, prefix_cache=False)
        eng.submit(_tokens(rng, 2 * BS), 6)
        eng.run()
        st = eng.stats()["prefix_cache"]
        assert not st["enabled"]
        assert st["hits"] == st["cached_tokens"] == 0
        assert len(eng._kinds[0].free) == eng.usable_blocks
        _assert_no_leaks(eng)


class TestTtft:
    def test_completions_carry_ttft(self):
        params = _params()
        rng = np.random.default_rng(65)
        eng = _engine(params)
        rid = eng.submit(_tokens(rng, 9), 4)
        eng.run()
        c = eng.completions[rid]
        assert c.ttft_s is not None and 0 < c.ttft_s <= c.latency_s
