"""The tower whose every layer runs grouped-query attention over the keys
a learned indexer keeps (workflow/sparse_gqa_lm.py), what it brought to
ops/attention.py (the kept keys' slots, a decode step that fetches the
kept rows alone, the walk under a mask shared with the latent rows) and to
the engine (the prefix cache for a tower that declares one kind of blocks
with two arrays in it), against the equations of
``benchmarks/reference/keye.py`` at a small size: 3 layers, hidden 64, 8
query heads over 2 K/V heads of 16, an indexer of 4 heads of 8 that keeps
16 keys, 16 experts of width 32 of which 3 a token, a block of 4,
vocabulary 256, float32 weights, seeded.

Tolerances: the program and the reference compute the same float32 sums in
different orders (blocked, through a cache, over fetched rows), which
moves a logit of size ~1 by ~1e-5; 3e-4 holds that with room, and a wrong
mask, a wrong selection or a missing norm moves logits by 1e-1 and more."""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from znicz_tpu import observability
from znicz_tpu.ops import attention as att
from znicz_tpu.services.engine import PagedDecodeEngine
from znicz_tpu.services.errors import (
    SpeculationUnsupportedError,
)
from znicz_tpu.workflow import sparse_gqa_lm as sgl
from znicz_tpu.workflow import window_lm

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BS, TOP_K = 4, 16
TOL = dict(rtol=3e-4, atol=3e-4)
SIZES = dict(d_model=64, vocab=256, d_ff_expert=32, n_experts=16)
CFG = {
    "name": "toy-keye", "model_type": "KeyeVL2", "hidden_size": 64,
    "num_hidden_layers": 3, "num_attention_heads": 8,
    "num_key_value_heads": 2, "head_dim": 16, "rope_theta": 1e7,
    "rope_scaling": {"mrope_section": [2, 3, 3], "rope_type": "default", "type": "default"},
    "sa_config": {
        "indexer_head_dim": 8, "indexer_num_heads": 4,
        "indexer_num_kv_heads": 1, "kv_chunk_size": 512, "q_chunk_size": 512,
        "topk": TOP_K,
    },
    "num_experts": 16, "num_experts_per_tok": 3, "norm_topk_prob": True,
    "decoder_sparse_step": 1, "mlp_only_layers": [], "rms_norm_eps": 1e-6,
    "use_sliding_window": False, "attention_bias": False, "vocab_size": 256,
}


def _load_reference():
    spec = importlib.util.spec_from_file_location(
        "keye_reference_for_tests",
        os.path.join(REPO, "benchmarks", "reference", "keye.py"),
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    module.BLOCK, module.QBLOCK = 16, 8  # its row blocks, at toy length
    return module


class Toy:
    def __init__(self, seed=1):
        self.ref = _load_reference()
        self.cfg = CFG
        self.model = sgl.SparseGQAMoEModel.from_config(CFG, max_positions=256)
        self.params = sgl.init_params(self.model, seed=seed, **SIZES)
        # jitted as the engine jits them: eager, a step is thousands of calls
        self.prefill_chunk = jax.jit(
            self.model.prefill_chunk, static_argnames=("block_size",)
        )
        self.decode_step = jax.jit(
            self.model.decode_step, static_argnames=("block_size",)
        )
        self.w = {
            "embed": self.params[0]["embed"], "blocks": self.params[1:-1],
            "final_norm": self.params[-1]["final_norm"],
            "head": self.params[-1]["head"],
        }

    def reference_logits(self, tokens, **kwargs):
        kwargs.setdefault("pad_to", 96)
        return np.asarray(self.ref.logits(self.cfg, self.w, list(tokens), **kwargs))

    def engine(self, **kw):
        kw.setdefault("batch_size", 2)
        kw.setdefault("max_seq", 128)
        kw.setdefault("admit_every", 4)
        return PagedDecodeEngine(
            self.params, n_heads=8, eos_id=0, block_size=BS, model=self.model,
            **kw
        )

    def served_gaps(self, completion):
        """How far each served token lies below the reference's best: 0
        everywhere when the engine served the reference's greedy."""
        seq = list(completion.tokens)
        n_prompt = len(seq) - completion.n_new
        # one padded length and one count of rows: one compiled reference
        logits = self.ref.logits(
            self.cfg, self.w, seq[:-1], first_row=n_prompt - 1, pad_to=128,
            rows_pad_to=48,
        )
        return np.asarray(self.ref.served_gaps(logits, seq[n_prompt:]))


@pytest.fixture(scope="module")
def toy():
    return Toy()


def _tokens(rng, n):
    return rng.integers(1, SIZES["vocab"], n)


def _counter(name, **labels):
    want = {k: str(v) for k, v in labels.items()}
    series = observability.get_registry().snapshot().get(name, {"series": []})
    return sum(
        s["value"] for s in series["series"]
        if want.items() <= {k: str(v) for k, v in s["labels"].items()}.items()
    )


# -- the ops ----------------------------------------------------------------


def _paged_rows(rng, lengths, *, g=2, d=16, m=12, bs=BS):
    b = len(lengths)
    pool = jnp.asarray(rng.standard_normal((b * m + 1, bs, 2 * g * d)), jnp.float32)
    table = jnp.asarray(1 + rng.permutation(b * m).reshape(b, m), jnp.int32)
    return pool, table, jnp.asarray(lengths, jnp.int32)


@pytest.mark.parametrize("top_k", [1, 5, 16], ids=["one", "five", "sixteen"])
def test_the_kept_keys_slots_list_the_mask_in_order(top_k):
    rng = np.random.default_rng(top_k)
    lengths = [37, 0, 12, 48, 3]
    keep = np.zeros((len(lengths), 48), bool)
    for b, n in enumerate(lengths):
        if n:
            keep[b, rng.choice(n, min(top_k, n), replace=False)] = True
    entry, offset, named = att.kept_key_slots(jnp.asarray(keep), top_k, block_size=BS)
    for b in range(len(lengths)):
        want = np.flatnonzero(keep[b])
        got = np.asarray(entry[b]) * BS + np.asarray(offset[b])
        assert int(named[b].sum()) == len(want)
        np.testing.assert_array_equal(got[np.asarray(named[b])], want)
        assert not got[~np.asarray(named[b])].any()  # the null block's first row


def test_a_row_no_longer_than_top_k_attends_like_full_grouped_query_attention():
    rng = np.random.default_rng(2)
    pool, table, lengths = _paged_rows(rng, [37, 0, 12])
    q = jnp.asarray(rng.standard_normal((3, 1, 8, 16)), jnp.float32)
    pos = jnp.maximum(lengths - 1, 0)[:, None]
    every = (jnp.arange(48)[None, :] < lengths[:, None])[:, None]
    full = att.paged_gqa_attention(
        q, pool, table, pos, block_size=BS, n_kv_heads=2, lengths=lengths
    )
    for form in (dict(lengths=lengths), {}):  # the fetch, the walk
        kept = att.kept_gqa_attention(
            q, pool, table, pos, every, block_size=BS, n_kv_heads=2, top_k=40,
            scale=0.25, **form
        )
        live = np.asarray(lengths) > 0
        np.testing.assert_allclose(kept[live], full[live], **TOL)


def test_the_decode_fetch_and_the_prefill_walk_agree_on_the_same_keep():
    rng = np.random.default_rng(3)
    pool, table, lengths = _paged_rows(rng, [41, 0, 9, 48])
    keep = np.zeros((4, 48), bool)
    for b, n in enumerate(np.asarray(lengths)):
        if n:
            keep[b, rng.choice(n, min(TOP_K, n), replace=False)] = True
    keep = jnp.asarray(keep)[:, None]
    q = jnp.asarray(rng.standard_normal((4, 1, 8, 16)), jnp.float32)
    pos = jnp.maximum(lengths - 1, 0)[:, None]
    args = dict(block_size=BS, n_kv_heads=2, top_k=TOP_K, scale=0.25)
    fetched = att.kept_gqa_attention(q, pool, table, pos, keep, lengths=lengths, **args)
    walked = att.kept_gqa_attention(q, pool, table, pos, keep, **args)
    np.testing.assert_allclose(fetched, walked, **TOL)
    assert not np.asarray(fetched[1]).any()  # a row that idles: zeros
    # a plain loop over heads and kept keys
    rows = np.asarray(pool)[np.asarray(table)].reshape(4, 48, 2, 2, 16)
    for b in (0, 2, 3):
        at = np.flatnonzero(np.asarray(keep[b, 0]))
        for h in range(8):
            k, v = rows[b, at, 1, h // 4], rows[b, at, 0, h // 4]
            s = k @ np.asarray(q[b, 0, h]) * 0.25
            p = np.exp(s - s.max())
            np.testing.assert_allclose(
                fetched[b, 0, h * 16:(h + 1) * 16], (p / p.sum()) @ v, **TOL
            )
    assert int(att.kept_rows_fetched(lengths, TOP_K)) == 16 + 0 + 9 + 16


# -- a decode step's selection, over the live rows alone ---------------------

ROWS = att.SELECT_TILE_ROWS
N_SLOTS = 2 * ROWS + 3  # whole tiles and a part of one


def _decode_scores(rng, live, *, n_slots=N_SLOTS, m=12):
    """Index scores of a decode step with ``live`` of ``n_slots`` rows
    decoding, scattered among idle ones: ``(scores [B, 1, m * BS], lengths,
    table)``.  Five distinct values, so the cut falls among equal scores;
    the first live row sees fewer keys than are kept, the second the whole
    table."""
    n_keys = m * BS
    lengths = np.zeros(n_slots, np.int32)
    where = rng.permutation(n_slots)[:live]
    lengths[where] = rng.integers(TOP_K, n_keys + 1, live)
    lengths[where[:1]] = TOP_K - 3
    lengths[where[1:2]] = n_keys
    values = rng.integers(0, 5, (n_slots, 1, n_keys)).astype(np.float32)
    seen = np.arange(n_keys) < lengths[:, None, None]
    table = 1 + rng.permutation(n_slots * m).reshape(n_slots, m)
    return (
        jnp.asarray(np.where(seen, values, -np.inf)), jnp.asarray(lengths),
        jnp.asarray(table, jnp.int32),
    )


def _whole_batch_selection(scores, lengths, top_k, *, block_table, block_size):
    """``select_live_rows`` as it was before PR 45: every row of the batch,
    live or idle, through ``select_top_keys`` and the listing."""
    keep, *sums = att._select_every_row(scores, top_k)
    listed = att.kept_row_addresses(
        keep[:, 0], block_table, top_k, block_size=block_size
    )
    return (listed, *sums)


@pytest.mark.parametrize(
    "live", [0, 1, ROWS - 1, ROWS, ROWS + 1, N_SLOTS],
    ids=["none", "one", "tile-less-one", "tile", "tile-and-one", "all"],
)
def test_the_live_rows_are_listed_as_the_whole_batch_lists_them(live):
    """EQUAL, not close, on every row: a live row's kept keys in order at
    the pool rows they lie at, nothing named for an idle one, with rows
    that see fewer keys than are kept and ties at the cut."""
    rng = np.random.default_rng(40 + live)
    scores, lengths, table = _decode_scores(rng, live)
    (want_address, want_named), want_scored, want_selected, _ = (
        _whole_batch_selection(
            scores, lengths, TOP_K, block_table=table, block_size=BS
        )
    )
    (address, named), scored, selected, visited = att.select_live_rows(
        scores, lengths, TOP_K, block_table=table, block_size=BS
    )
    np.testing.assert_array_equal(named, want_named)
    np.testing.assert_array_equal(address, want_address)
    idle = np.asarray(lengths) == 0
    assert not np.asarray(named)[idle].any() and not np.asarray(address)[idle].any()
    assert np.asarray(named)[~idle].sum(axis=-1).tolist() == [
        min(int(n), TOP_K) for n in np.asarray(lengths)[~idle]
    ]
    assert int(scored) == int(want_scored) == int(np.asarray(lengths).sum())
    assert int(selected) == int(want_selected)
    assert int(visited) == -(-live // ROWS) * ROWS
    if live > 1:  # the cut of the row that sees the whole table is a tie
        row = np.sort(np.asarray(scores)[np.asarray(lengths).argmax(), 0])[::-1]
        assert row[TOP_K - 1] == row[TOP_K]


def _random_decode_step(toy, rng, live, n_slots):
    """The operands of one decode step over pools of random rows: ``live``
    of ``n_slots`` rows decode at random positions, scattered."""
    m = 16
    pools = jax.tree.map(
        lambda p: jnp.asarray(rng.standard_normal(p.shape), p.dtype),
        toy.model.init_pools(toy.params, {"global": n_slots * m + 1}, BS),
    )
    table = 1 + rng.permutation(n_slots * m).reshape(n_slots, m)
    mask = np.zeros(n_slots, bool)
    mask[rng.permutation(n_slots)[:live]] = True
    pos = np.where(mask, rng.integers(0, m * BS, n_slots), 0)
    return (
        pools, {"global": jnp.asarray(table, jnp.int32)},
        jnp.asarray(_tokens(rng, n_slots)), jnp.asarray(pos, jnp.int32),
    ), jnp.asarray(mask)


@pytest.mark.parametrize("live", [1, ROWS + 1], ids=["one", "tile-and-one"])
def test_a_decode_step_is_the_step_that_selected_over_every_slot(
    toy, live, monkeypatch
):
    """The tower's step with the selection tiled over its live rows gives
    the logits, the pools and the sums of the step that selected and listed
    over the whole batch, to the bit."""
    args, mask = _random_decode_step(toy, np.random.default_rng(50 + live), live, ROWS + 3)

    def step():
        # a new function: a new trace, whatever is patched
        return jax.jit(
            lambda *a, **kw: toy.model.decode_step(*a, **kw),
            static_argnames=("block_size",),
        )(toy.params, *args, block_size=BS, write_mask=mask)

    pools, logits, load = step()
    monkeypatch.setattr(att, "select_live_rows", _whole_batch_selection)
    want_pools, want_logits, want_load = step()
    np.testing.assert_array_equal(logits, want_logits)
    jax.tree.map(np.testing.assert_array_equal, pools, want_pools)
    assert int(load.pop("sparse_rows")) == -(-live // ROWS) * ROWS
    assert int(want_load.pop("sparse_rows")) == ROWS + 3
    jax.tree.map(np.testing.assert_array_equal, load, want_load)


# -- the tower against the reference -----------------------------------------


class _Row:
    """What the engine keeps for one row, by hand."""

    def __init__(self, toy, n_blocks=64):
        self.toy = toy
        self.pools = toy.model.init_pools(toy.params, {"global": n_blocks}, BS)
        self.table = np.zeros(32, np.int32)
        self.next = 1

    def ensure(self, last_pos):
        for blk in range(last_pos // BS + 1):
            if self.table[blk] == 0:
                self.table[blk] = self.next
                self.next += 1

    def prefill(self, prompt):
        padded = -(-len(prompt) // BS) * BS
        tokens = np.zeros(padded, np.int32)
        tokens[: len(prompt)] = prompt
        for c in range(padded // BS):
            self.ensure((c + 1) * BS - 1)
            last = (len(prompt) - 1) % BS if c == padded // BS - 1 else BS - 1
            self.pools, logits, load = self.toy.prefill_chunk(
                self.toy.params, self.pools, {"global": jnp.asarray(self.table)},
                jnp.asarray(tokens[None, c * BS:(c + 1) * BS]),
                jnp.int32(c * BS), block_size=BS, last=jnp.int32(last),
            )
        return logits, load

    def decode(self, token, pos):
        self.ensure(pos)
        t = jnp.asarray(self.table)
        # a second, idle row rides along: it writes to the null block,
        # scores no key and is routed nowhere
        self.pools, logits, load = self.toy.decode_step(
            self.toy.params, self.pools,
            {"global": jnp.stack([t, jnp.zeros_like(t)])},
            jnp.asarray([token, 0]), jnp.asarray([pos, 0]), block_size=BS,
            write_mask=jnp.asarray([True, False]),
        )
        return logits[0], load


@pytest.mark.parametrize(
    "n_prompt, n_total", [(7, 13), (11, 30), (50, 75), (21, 27)],
    ids=["under-top-k", "crosses-top-k-while-decoding", "far-past-top-k",
         "crosses-top-k-in-prefill"],
)
def test_prefill_chunks_then_decode_steps_match_the_reference_forward(
    toy, n_prompt, n_total
):
    seq = _tokens(np.random.default_rng(10 + n_prompt), n_total)
    want = toy.reference_logits(seq)
    row = _Row(toy)
    logits, load = row.prefill(seq[:n_prompt])
    np.testing.assert_allclose(logits[0], want[n_prompt - 1], **TOL)
    first = (n_prompt - 1) // BS * BS
    assert int(load["sparse_scored"]) == sum(range(first + 1, first + BS + 1))
    assert int(load["sparse_selected"]) == sum(
        min(n, TOP_K) for n in range(first + 1, first + BS + 1)
    )
    for pos in range(n_prompt, n_total):
        logits, load = row.decode(seq[pos], pos)
        np.testing.assert_allclose(logits, want[pos], **TOL)
        assert int(jnp.sum(load["pairs"])) <= 3 * 3  # one live row, 3 layers
        assert int(load["sparse_scored"]) == pos + 1
        # the fetch names the kept rows and nothing else, whatever the length
        assert int(load["sparse_selected"]) == min(pos + 1, TOP_K)
        assert int(load["cached_rows"]) == min(pos + 1, TOP_K)
        assert int(load["cached_rows_by_kind"]["global"]) == min(pos + 1, TOP_K)


def test_the_reference_after_a_prefix_state_is_the_reference_in_one_pass(toy):
    seq = _tokens(np.random.default_rng(4), 90)
    whole = toy.reference_logits(seq, first_row=50)
    state = toy.ref.prefix_state(toy.cfg, toy.w, list(seq[:40]))
    tail = toy.reference_logits(seq[40:], state=state, first_row=10)
    np.testing.assert_allclose(tail, whole, rtol=1e-5, atol=1e-5)


def test_the_selection_matters_and_each_control_moves_the_logits(toy):
    seq = _tokens(np.random.default_rng(5), 80)
    want = toy.reference_logits(seq, first_row=60)
    state = toy.ref.prefix_state(toy.cfg, toy.w, list(seq[:40]))
    for control in (
        dict(select="all"), dict(select="recent"), dict(index_topk=TOP_K // 2),
    ):
        moved = toy.reference_logits(seq, first_row=60, **control)
        assert np.abs(moved - want).max() > 0.05, control
    blind = toy.reference_logits(
        seq[40:], state=state, first_row=20, past_index="zero"
    )
    assert np.abs(blind - want).max() > 0.05


# -- through the engine -----------------------------------------------------


def test_the_engine_serves_the_reference_greedy_and_fetches_the_kept_rows(toy):
    rng = np.random.default_rng(12)
    eng = toy.engine()
    (kind,) = eng._kinds
    assert kind.name == "global" and kind.window is None and eng.prefix_cache
    # two arrays a block: [v, k] rows and, beside them, the indexer's keys
    # in a whole 128-lane tile
    assert eng._pools[0]["kv"].shape == (eng.n_blocks["global"], BS, 64)
    assert eng._pools[0]["idx"].shape == (eng.n_blocks["global"], BS, 128)
    assert eng.block_bytes == kind.block_bytes == 3 * BS * (64 + 128) * 4
    selected0 = _counter("znicz_serve_sparse_keys_selected_total", phase="decode")
    scored0 = _counter("znicz_serve_sparse_keys_scored_total", phase="decode")
    rows0 = _counter("znicz_serve_decode_cached_rows_total", kind="global")
    steps0 = _counter("znicz_serve_decode_steps_total")
    ids = [
        eng.submit(_tokens(rng, n), new)
        for n, new in ((9, 5), (70, 12), (13, 19), (33, 30))
    ]
    eng.run()
    for rid in ids:
        completion = eng.completions[rid]
        assert completion.finish_reason in ("budget", "eos")
        assert toy.served_gaps(completion).max() < 1e-4
    selected = _counter(
        "znicz_serve_sparse_keys_selected_total", phase="decode"
    ) - selected0
    scored = _counter("znicz_serve_sparse_keys_scored_total", phase="decode") - scored0
    rows = _counter("znicz_serve_decode_cached_rows_total", kind="global") - rows0
    steps = _counter("znicz_serve_decode_steps_total") - steps0
    assert 0 < selected < scored
    # a step a layer fetches the kept rows: at most live rows x top_k
    assert rows == selected and rows <= steps * 2 * TOP_K


def _deltas(*names_and_labels):
    """A function that reads how far the named counters moved since."""
    def read():
        return [_counter(name, **labels) for name, labels in names_and_labels]
    before = read()
    return lambda: [now - then for now, then in zip(read(), before)]


def test_batches_of_one_nine_and_every_slot_share_one_decode_program(toy):
    """The selection's loop follows the live rows INSIDE the compiled
    decode chunk: 1, 9 and all 12 slots decoding run the one program, each
    answer the reference's greedy."""
    from znicz_tpu.services import engine as engine_module

    rng = np.random.default_rng(60)
    # a tick prefills every waiting prompt, so a batch decodes together
    eng = toy.engine(batch_size=12, prefix_cache=False, prefill_budget=512)
    compiled = []
    for batch in (1, 9, 12):
        moved = _deltas(
            ("znicz_serve_sparse_rows_selected_total", dict(phase="decode")),
            ("znicz_serve_decode_steps_total", {}),
        )
        ids = [
            # 9 to 16 blocks a row, so every batch decodes at the rung of
            # 16, and answers long enough for the batch to decode together
            eng.submit(_tokens(rng, int(rng.integers(33, 37))), int(rng.integers(24, 29)))
            for _ in range(batch)
        ]
        eng.run()
        for rid in ids:
            assert toy.served_gaps(eng.completions[rid]).max() < 1e-4
        rows, steps = moved()
        # whole tiles of the rows that decoded: one tile while at most 8
        # did, two while more
        assert ROWS * steps <= rows <= -(-batch // ROWS) * ROWS * steps
        assert (rows > ROWS * steps) == (batch > ROWS)
        compiled.append((
            _counter("znicz_serve_compiles_total", kind="decode"),
            engine_module._paged_decode_chunk._cache_size(),
        ))
    assert compiled[0] == compiled[1] == compiled[2]
    programs = eng.compile_stats()["programs"]
    assert [key[:2] for key in programs if key[0] == "paged_chunk"] == [
        ("paged_chunk", 4)
    ]


def test_the_rows_the_selection_visits_are_counted_by_phase(toy):
    """``znicz_serve_sparse_rows_selected_total{phase}``: a prefill chunk
    counts its one row, a decode step of one request one tile of rows (a
    layer's, as the two counters of keys are)."""
    eng = toy.engine(batch_size=12, prefix_cache=False)
    moved = _deltas(
        ("znicz_serve_sparse_rows_selected_total", dict(phase="prefill")),
        ("znicz_serve_prefill_chunks_total", {}),
        ("znicz_serve_sparse_rows_selected_total", dict(phase="decode")),
        ("znicz_serve_decode_steps_total", {}),
    )
    eng.submit(_tokens(np.random.default_rng(61), 21), 9)
    eng.run()
    prefill_rows, chunks, decode_rows, steps = moved()
    assert prefill_rows == chunks == -(-21 // BS)
    assert steps > 0 and decode_rows == ROWS * steps


def test_a_request_after_a_prefix_hit_gets_the_logits_of_the_cold_request(toy):
    """The shared blocks carry the indexer's keys beside K/V: a request
    that maps a cached chain is served the reference's greedy, as the cold
    one was, far past top-k (a block whose indexer keys were zeros would
    be selected by position, not by score)."""
    rng = np.random.default_rng(14)
    shared = _tokens(rng, 64)
    eng = toy.engine()
    hits0 = _counter("znicz_serve_prefix_hits_total")
    cached0 = _counter("znicz_serve_prefix_cached_tokens_total")
    prompt = np.concatenate([shared, _tokens(rng, 7)])
    cold = eng.submit(prompt, 12)
    eng.run()
    assert _counter("znicz_serve_prefix_hits_total") == hits0
    warm = eng.submit(prompt, 12)
    other = eng.submit(np.concatenate([shared, _tokens(rng, 10)]), 9)
    eng.run()
    assert _counter("znicz_serve_prefix_hits_total") - hits0 >= 2 * 16
    assert _counter("znicz_serve_prefix_cached_tokens_total") - cached0 >= 2 * 64
    np.testing.assert_array_equal(
        eng.completions[warm].tokens, eng.completions[cold].tokens
    )
    for rid in (cold, warm, other):
        assert toy.served_gaps(eng.completions[rid]).max() < 1e-4


def test_a_copy_on_write_split_carries_the_indexer_keys(toy):
    """A prompt that is whole cached blocks re-runs its last block's chunk
    into a fresh block, and a second row that decodes into a cached,
    partly filled block splits it with a copy: both arrays of the block
    are copied, and the answers stay the reference's."""
    rng = np.random.default_rng(15)
    eng = toy.engine()
    cow0 = eng._n_cow
    prompt = _tokens(rng, 40)  # ten whole blocks
    first = eng.submit(prompt, 10)  # decodes into blocks of its own
    eng.run()
    # the same prompt plus what the first answered, cut inside a block the
    # first request published: the tail decodes INTO a mapped, shared block
    grown = np.asarray(eng.completions[first].tokens[:46])
    second = eng.submit(grown, 8)
    again = eng.submit(prompt, 10)
    eng.run()
    assert eng._n_cow > cow0
    np.testing.assert_array_equal(
        eng.completions[again].tokens, eng.completions[first].tokens
    )
    for rid in (first, second, again):
        assert toy.served_gaps(eng.completions[rid]).max() < 1e-4
    src, dst = 3, 5
    pools = [
        {k: a.at[src].set(1.0 + i) for i, (k, a) in enumerate(p.items())}
        for p in eng._pools
    ]
    from znicz_tpu.workflow.generate import copy_paged_block

    for pool in copy_paged_block(pools, jnp.int32(src), jnp.int32(dst)):
        assert set(pool) == {"kv", "idx"}
        for name, a in pool.items():
            np.testing.assert_array_equal(a[dst], a[src])


def test_a_preempted_row_is_readmitted_and_still_serves_the_reference(toy):
    rng = np.random.default_rng(13)
    eng = toy.engine(n_blocks={"global": 24})
    before = _counter("znicz_serve_preemptions_total")
    ids = [eng.submit(_tokens(rng, n), 40) for n in (38, 30)]
    eng.run()
    assert _counter("znicz_serve_preemptions_total") > before
    for rid in ids:
        assert toy.served_gaps(eng.completions[rid]).max() < 1e-4


def test_the_prefix_cache_is_on_by_default_only_where_no_kind_gives_blocks_back(toy):
    """Decided from the kinds' windows: this tower's one kind keeps its
    blocks, a tower with a window kind does not."""
    before = {
        f: _counter("znicz_serve_unsupported_total", feature=f)
        for f in ("speculation", "prefix_cache")
    }
    assert toy.engine(prefix_cache=True).prefix_cache
    assert not toy.engine(prefix_cache=False).prefix_cache
    with pytest.raises(SpeculationUnsupportedError):
        toy.engine(spec_k=2)
    with pytest.raises(ValueError, match="by kind"):
        toy.engine(n_blocks=64)
    windowed = window_lm.WindowGQAMoEModel(
        n_heads=4, n_kv_heads=2, head_dim=16, top_k=2, window=9,
        windowed=(True, False), max_positions=128,
    )
    params = window_lm.init_params(
        windowed, d_model=64, vocab=256, d_ff_expert=32, n_experts=4
    )

    def build(**kw):
        return PagedDecodeEngine(
            params, n_heads=4, eos_id=0, block_size=BS, max_seq=64,
            batch_size=2, model=windowed, **kw
        )

    assert not build().prefix_cache
    assert build(prefix_cache=True).prefix_cache  # served when named
    assert _counter(
        "znicz_serve_unsupported_total", feature="speculation"
    ) == before["speculation"] + 1
    assert _counter(
        "znicz_serve_unsupported_total", feature="prefix_cache"
    ) == before["prefix_cache"]


def test_from_config_reads_the_published_keys_and_refuses_what_is_not_built():
    cfg_path = os.path.join(
        REPO, "benchmarks", "configs", "keye-vl2-30b-a3b-l6.json"
    )
    import json

    with open(cfg_path) as f:
        cfg = json.load(f)
    model = sgl.SparseGQAMoEModel.from_config(cfg, max_positions=69632)
    assert (model.n_layers, model.n_heads, model.n_kv_heads, model.head_dim) == (6, 32, 4, 128)
    assert (model.index_n_heads, model.index_head_dim, model.index_topk) == (16, 64, 2048)
    assert model.top_k == 8 and model.rope_theta == 1e7 and model.index_row_width == 128
    assert model.cache_kinds[0].window is None and model.layer_kinds == ("global",) * 6
    for change in (
        {"rope_scaling": {"rope_type": "yarn", "factor": 4.0}},
        {"mlp_only_layers": [0]}, {"use_sliding_window": True},
        {"sa_config": dict(cfg["sa_config"], indexer_num_kv_heads=2)},
    ):
        with pytest.raises(ValueError):
            sgl.SparseGQAMoEModel.from_config({**cfg, **change}, max_positions=1024)
