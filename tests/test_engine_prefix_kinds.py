"""The prefix cache for a tower with a WINDOW kind of cache blocks
(services/engine.py: a chain has a holder a kind, global blocks for the
whole match, window blocks for its last window), on the toy towers of
tests/test_gated_window_lm.py (window 16 at a block of 4: a ring of 6),
tests/test_window_gqa_lm.py and tests/test_sparse_latent_lm.py.

A request that maps a cached chain must be served as one that prefilled
every token: the served tokens are the cache-off engine's and lie at the
plain reference's best logit (gaps under 1e-4: a window layer handed the
wrong rows, or rows their owner gave back, moves logits by 1e-1)."""

import numpy as np
import pytest

from test_gated_window_lm import BS, WINDOW, Toy, _counter, _tokens
from test_sparse_latent_lm import Toy as SparseToy
from test_window_gqa_lm import Toy as WindowToy
from znicz_tpu.services.errors import (
    PrefixCacheUnsupportedError,
    SpeculationUnsupportedError,
)


@pytest.fixture(scope="module")
def toy():
    return Toy()


def _kinds(eng):
    return {k.name: k for k in eng._kinds}


def _digests(eng, tokens):
    return list(eng._chain_hashes(np.asarray(tokens, np.int32)))


def _prime(eng, prefix):
    """The priming call: a request that IS the prefix, retired."""
    rid = eng.submit(prefix, max_new_tokens=1)
    eng.run()
    assert eng.completions[rid].finish_reason in ("budget", "eos")


def _assert_no_leaks(eng):
    assert eng.active == 0 and eng.prefilling == 0 and eng.pending == 0
    eng.flush_prefix_cache()
    for kind in eng._kinds:
        assert not kind.cache and not kind.block_hash and not kind.lru
        assert sorted(kind.free) == list(range(1, kind.n_blocks))
        assert (kind.ref == 0).all()


def _mapped():
    return {
        kind: _counter("znicz_serve_prefix_blocks_mapped_total", kind=kind)
        for kind in ("global", "window")
    }


@pytest.mark.parametrize(
    "n_prefix,window_blocks", [(8, 2), (16, 4), (40, 4)],
    ids=["shorter_than_the_window", "the_window", "longer_than_the_window"],
)
def test_a_request_that_shares_a_primed_prefix_is_served_as_with_the_cache_off(
    toy, n_prefix, window_blocks
):
    rng = np.random.default_rng(n_prefix)
    prefix = _tokens(rng, n_prefix)
    prompts = [np.concatenate([prefix, _tokens(rng, n)]) for n in (7, 3)]
    cold = toy.engine()
    cold_ids = [cold.submit(p, max_new_tokens=9) for p in prompts]
    cold.run()

    eng = toy.engine(prefix_cache=True)
    assert eng.prefix_cache
    _prime(eng, prefix)
    kinds = _kinds(eng)
    # the global kind holds every block of the prefix, the window kind the
    # ones the priming row still held: those within the window of its end
    n = n_prefix // BS
    assert len(kinds["global"].cache) == n
    assert len(kinds["window"].cache) == min(n, WINDOW // BS + 1)
    assert eng.prefix_probe(prompts[0])["cached_blocks"] == n
    before, cached = _mapped(), _counter("znicz_serve_prefix_cached_tokens_total")
    ids = [eng.submit(p, max_new_tokens=9) for p in prompts]
    eng.run()
    for rid, cold_id in zip(ids, cold_ids):
        done = eng.completions[rid]
        np.testing.assert_array_equal(done.tokens, cold.completions[cold_id].tokens)
        assert toy.served_gaps(done).max() < 1e-4
        assert done.timings["cached_tokens"] == n_prefix
    after = _mapped()
    assert after["global"] - before["global"] == 2 * n
    assert after["window"] - before["window"] == 2 * window_blocks
    assert _counter("znicz_serve_prefix_cached_tokens_total") - cached == 2 * n_prefix
    _assert_no_leaks(eng)


def test_a_prompt_that_is_wholly_cached_stops_a_block_short_and_splits_nothing(toy):
    """Several kinds: the match stops short of the prompt's last token, so
    the final chunk opens a fresh block in every kind and no block is
    split."""
    prefix = _tokens(np.random.default_rng(2), 24)
    eng = toy.engine(prefix_cache=True)
    _prime(eng, prefix)
    rid = eng.submit(prefix, max_new_tokens=6)
    eng.run()
    done = eng.completions[rid]
    assert done.timings["cached_tokens"] == 20
    assert toy.served_gaps(done).max() < 1e-4
    assert eng.stats()["prefix_cache"]["cow_splits"] == 0
    _assert_no_leaks(eng)


def test_a_chain_whose_window_holder_went_is_cut_and_counted(toy):
    rng = np.random.default_rng(3)
    prefix = _tokens(rng, 40)
    prompt = np.concatenate([prefix, _tokens(rng, 6)])
    eng = toy.engine(prefix_cache=True)
    _prime(eng, prefix)
    window = _kinds(eng)["window"]
    digests = _digests(eng, prefix)
    # the priming row held blocks 5-9 when it retired; a match of 10 blocks
    # reads 6-9 of them, one of 9 blocks 5-8
    assert [h in window.cache for h in digests] == [False] * 5 + [True] * 5
    window.forget(digests[9])
    before = _counter("znicz_serve_prefix_chain_cut_total", reason="window_not_held")
    rid = eng.submit(prompt, max_new_tokens=8)
    eng.run()
    done = eng.completions[rid]
    assert done.timings["cached_tokens"] == 9 * BS
    assert toy.served_gaps(done).max() < 1e-4
    cut = "znicz_serve_prefix_chain_cut_total"
    assert _counter(cut, reason="window_not_held") == before + 1
    # every window holder gone: the match is lost, and counted once more
    eng.flush_prefix_cache()
    _prime(eng, prefix)
    for h in digests:
        window.forget(h)
    rid = eng.submit(prompt, max_new_tokens=8)
    eng.run()
    assert eng.completions[rid].timings["cached_tokens"] == 0
    assert toy.served_gaps(eng.completions[rid]).max() < 1e-4
    assert _counter(cut, reason="window_not_held") == before + 2
    _assert_no_leaks(eng)


def test_evicting_a_global_block_drops_the_window_holder_of_its_hash(toy):
    prefix = _tokens(np.random.default_rng(4), 40)
    eng = toy.engine(prefix_cache=True)
    _prime(eng, prefix)
    kinds = _kinds(eng)
    digests = _digests(eng, prefix)
    free_before = len(kinds["window"].free)
    kinds["global"].free.clear()  # a dry free list: allocation evicts
    evicted = {
        kinds["global"].block_hash[blk] for blk in list(kinds["global"].lru)[:7]
    }
    for _ in range(7):
        assert eng._alloc_block(kinds["global"]) > 0
    # release order put the prefix's LAST blocks first in the LRU: 9 .. 3
    assert evicted == set(digests[3:])
    assert not any(h in kinds["window"].cache for h in digests)
    assert len(kinds["window"].free) == free_before + 5
    # the global kind still holds blocks 0-2, but a match of 3 blocks reads
    # the window kind's 0-2, which no row held when it retired
    assert eng.prefix_probe(prefix)["cached_blocks"] == 0


def test_a_shared_window_block_is_given_back_by_count_never_under_another_row(toy):
    rng = np.random.default_rng(5)
    prefix = _tokens(rng, 40)
    eng = toy.engine(prefix_cache=True, batch_size=2)
    _prime(eng, prefix)
    window = _kinds(eng)["window"]
    shared = [window.cache[h] for h in _digests(eng, prefix)[6:]]
    first = eng.submit(np.concatenate([prefix, _tokens(rng, 3)]), max_new_tokens=18)
    second = eng.submit(np.concatenate([prefix, _tokens(rng, 17)]), max_new_tokens=4)
    eng._admit_pending()
    assert [int(window.ref[b]) for b in shared] == [2] * 4
    seen_single = False
    while eng._has_work():
        eng.tick()
        for blk in shared:
            # never on the free list while it is cached, and never freed
            # under a row that still maps it
            assert blk not in window.free
            held = sum(blk in row for row in window.row_blocks)
            assert int(window.ref[blk]) == held
            seen_single |= held == 1
    assert seen_single  # one row slid past it while the other still read it
    for rid in (first, second):
        assert toy.served_gaps(eng.completions[rid]).max() < 1e-4
    assert all(b in window.lru for b in shared)  # cache-only again
    _assert_no_leaks(eng)


def test_a_preempted_row_that_maps_shared_blocks_is_readmitted_from_the_cache(toy):
    rng = np.random.default_rng(7)
    prefix = _tokens(rng, 24)
    eng = toy.engine(prefix_cache=True, n_blocks={"global": 16, "window": 13})
    _prime(eng, prefix)
    before = _counter("znicz_serve_preemptions_total")
    ids = [
        eng.submit(np.concatenate([prefix, _tokens(rng, n)]), max_new_tokens=22)
        for n in (5, 9)
    ]
    eng.run()
    assert _counter("znicz_serve_preemptions_total") > before
    for rid in ids:
        assert toy.served_gaps(eng.completions[rid]).max() < 1e-4
    # the readmitted row mapped the prefix (and its own published blocks) again
    assert max(eng.completions[rid].timings["cached_tokens"] for rid in ids) > 24
    _assert_no_leaks(eng)


@pytest.mark.parametrize("tower", [WindowToy, SparseToy], ids=["smallthinker", "dots3"])
def test_left_to_its_default_a_window_tower_hashes_nothing(tower, monkeypatch):
    toy = tower()
    eng = toy.engine()
    assert eng.prefix_cache is False

    def no_hashing(*a, **kw):
        raise AssertionError("a tower served without the prefix cache hashed")

    monkeypatch.setattr(type(eng), "_chain_hashes", no_hashing)
    rng = np.random.default_rng(7)
    prompt = rng.integers(1, 256, 30)
    ids = [eng.submit(prompt, max_new_tokens=6) for _ in range(2)]
    eng.run()
    for rid in ids:
        assert toy.served_gaps(eng.completions[rid]).max() < 1e-4
        assert eng.completions[rid].timings["cached_tokens"] == 0
    for kind in eng._kinds:
        assert not kind.cache and not kind.block_hash and not kind.lru
    assert eng.stats()["prefix_cache"]["enabled"] is False


@pytest.mark.parametrize("tower", [WindowToy, SparseToy], ids=["smallthinker", "dots3"])
def test_asked_for_by_name_the_other_window_towers_share_a_prefix_too(tower):
    """The engine decides from the kinds' windows alone, so the window
    towers the benchmark serves WITHOUT the cache get it for nothing when
    it is asked for: dots3's global blocks carry the indexer's keys beside
    the latent rows, and a shared block carries both."""
    toy = tower()
    rng = np.random.default_rng(8)
    prefix = rng.integers(1, 256, 40)
    eng = toy.engine(prefix_cache=True)
    _prime(eng, prefix)
    rid = eng.submit(
        np.concatenate([prefix, rng.integers(1, 256, 11)]), max_new_tokens=8
    )
    eng.run()
    done = eng.completions[rid]
    assert done.timings["cached_tokens"] == 40
    assert toy.served_gaps(done).max() < 1e-4
    _assert_no_leaks(eng)


def test_the_typed_refusals_that_remain(toy):
    before = {
        f: _counter("znicz_serve_unsupported_total", feature=f)
        for f in ("speculation", "prefix_cache")
    }
    with pytest.raises(SpeculationUnsupportedError, match="GatedWindowGQAMoEModel"):
        toy.engine(spec_k=2)
    with pytest.raises(ValueError, match="by kind"):
        toy.engine(n_blocks=64)
    # copy-on-write across kinds: a write into a shared block is refused by
    # type (a match is whole blocks and stops short of the prompt's end, so
    # serving never comes to it; here the block is published under the row)
    eng = toy.engine(prefix_cache=True)
    eng.submit(_tokens(np.random.default_rng(9), 10), max_new_tokens=8)
    eng._admit_pending()
    eng._prefill_tick()
    eng._publish_row(0)
    with pytest.raises(PrefixCacheUnsupportedError, match="copy-on-write across kinds"):
        eng._cow_split(0, 1, copy=True)
    after = {
        f: _counter("znicz_serve_unsupported_total", feature=f)
        for f in ("speculation", "prefix_cache")
    }
    assert after == {f: n + 1 for f, n in before.items()}
