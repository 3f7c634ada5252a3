"""The window/global, grouped-query, ReLU-gated-experts tower
(workflow/window_lm.py) and what it brought to ops/ and services/engine.py
(block tables by layer kind), against the equations of
``benchmarks/reference/smallthinker.py`` at a small size: 4 layers (global,
window, window, window: one whole period), hidden 64, 4 query heads of 16
over 2 K/V heads, 8 experts of 32 with 3 a token, vocabulary 256, float32
weights, seeded.  The window is 16 tokens at a block of 4, so a row of a
few dozen tokens crosses it, gives blocks back and wraps its ring table.

ONE fixture (``toy``) builds the model, its parameters and the reference's
view of the same arrays, so the program and the reference cannot drift
apart in a test of their own.

The model-configs guide's share-adds-up test does not apply: no share of a
layer is cut (every expert, every head and the whole vocabulary are held).

Tolerances: the program and the reference compute the same float32 sums in
different orders (blocked, grouped, through a cache), which moves a logit
of size ~1 by ~1e-5; 2e-4 holds that with room and a wrong mask, a wrong
rotation or a missing expert moves logits by 1e-1 and more."""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from znicz_tpu import observability
from znicz_tpu.ops import moe, rope
from znicz_tpu.ops.attention import (
    gqa_cache_row,
    paged_gqa_attention,
    ring_key_positions,
)
from znicz_tpu.services.engine import PagedDecodeEngine
from znicz_tpu.services.errors import (
    RequestTooLargeError,
    SpeculationUnsupportedError,
)
from znicz_tpu.workflow import window_lm
from znicz_tpu.workflow.generate import init_paged_kv
from znicz_tpu.workflow.transformer import init_lm_params

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BS = 4  # block size
WINDOW = 16
TOL = dict(rtol=2e-4, atol=2e-4)
SIZES = dict(d_model=64, vocab=256, d_ff_expert=32, n_experts=8)
CFG = {
    "name": "toy-smallthinker", "num_hidden_layers": 4, "hidden_size": 64,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "moe_num_primary_experts": 8, "moe_num_active_primary_experts": 3,
    "moe_ffn_hidden_size": 32, "moe_primary_router_apply_softmax": True,
    "norm_topk_prob": True, "rms_norm_eps": 1e-6, "rope_theta": 1500000,
    "rope_scaling": None, "sliding_window_size": WINDOW,
    # the published period, longer than the layers run: the first 4 count
    "sliding_window_layout": [0, 1, 1, 1, 0, 1, 1, 1],
    "rope_layout": [0, 1, 1, 1, 0, 1, 1, 1],
}


def _load_reference():
    spec = importlib.util.spec_from_file_location(
        "smallthinker_reference_for_tests",
        os.path.join(REPO, "benchmarks", "reference", "smallthinker.py"),
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    module.BLOCK = 8  # its row blocks, at toy length
    return module


class Toy:
    def __init__(self, seed=1, max_positions=128):
        self.ref = _load_reference()
        self.cfg = CFG
        self.model = window_lm.WindowGQAMoEModel.from_config(
            CFG, max_positions=max_positions
        )
        self.params = window_lm.init_params(self.model, seed=seed, **SIZES)
        self.w = {
            "embed": self.params[0]["embed"], "blocks": self.params[1:-1],
            "final_norm": self.params[-1]["final_norm"],
            "head": self.params[-1]["head"],
        }

    def reference_logits(self, tokens):
        return np.asarray(self.ref.logits(self.cfg, self.w, list(tokens)))

    def engine(self, **kw):
        kw.setdefault("batch_size", 2)
        kw.setdefault("max_seq", 64)
        kw.setdefault("admit_every", 4)
        return PagedDecodeEngine(
            self.params, n_heads=4, eos_id=0, block_size=BS,
            model=self.model, **kw
        )

    def served_gaps(self, completion):
        """How far each served token lies below the reference's best:
        0 everywhere when the engine served the reference's greedy."""
        seq = list(completion.tokens)
        n_prompt = len(seq) - completion.n_new
        logits = self.ref.logits(
            self.cfg, self.w, seq[:-1], first_row=n_prompt - 1
        )
        return np.asarray(self.ref.served_gaps(logits, seq[n_prompt:]))


@pytest.fixture(scope="module")
def toy():
    return Toy()


def _tokens(rng, n):
    return rng.integers(1, SIZES["vocab"], n)


def _series(name, **labels):
    want = {k: str(v) for k, v in labels.items()}
    series = observability.get_registry().snapshot().get(name, {"series": []})
    return [
        s for s in series["series"]
        if want.items() <= {k: str(v) for k, v in s["labels"].items()}.items()
    ]


def _counter(name, **labels):
    return sum(s["value"] for s in _series(name, **labels))


# -- the small pieces ------------------------------------------------------


def test_plain_rotary_frequencies_match_the_reference(toy):
    np.testing.assert_allclose(
        rope.plain_inv_freq(16, 1.5e6), toy.ref.inv_freq(CFG), rtol=1e-6
    )
    # the YaRN form with no stretch is the plain form
    np.testing.assert_allclose(
        rope.yarn_inv_freq(
            16, 1.5e6, factor=1.0, original_max=64, beta_fast=32, beta_slow=1
        ),
        rope.plain_inv_freq(16, 1.5e6), rtol=1e-6,
    )


def _paged_case(rng, lengths, *, heads=4, kv_heads=2, dim=16, window=None,
                width=6, n_blocks=40):
    """Rows of ``lengths`` cached tokens in a pool, through plain tables or
    ring tables of ``width``; the last token of each row is the query's."""
    b, t_max = len(lengths), max(lengths)
    k = rng.standard_normal((b, t_max, kv_heads, dim)).astype(np.float32)
    v = rng.standard_normal((b, t_max, kv_heads, dim)).astype(np.float32)
    q = rng.standard_normal((b, 1, heads, dim)).astype(np.float32)
    rows = np.asarray(gqa_cache_row(jnp.asarray(k), jnp.asarray(v)))
    pool = np.full((n_blocks, BS, 2 * kv_heads * dim), 1e4, np.float32)
    m = -(-t_max // BS) if window is None else width
    table = np.zeros((b, m), np.int32)
    free = iter(rng.permutation(np.arange(1, n_blocks)))
    for r, length in enumerate(lengths):
        first = 0 if window is None else max(length - window, 0) // BS
        for blk in range(first, -(-length // BS)):
            table[r, blk % m] = pid = next(free)
            for o in range(BS):
                if blk * BS + o < length:
                    pool[pid, o] = rows[r, blk * BS + o]
    pos = np.asarray(lengths)[:, None] - 1
    return q, k, v, jnp.asarray(pool), jnp.asarray(table), jnp.asarray(pos)


def _per_head_loop(q, k, v, length, window):
    heads, kv_heads, dim = q.shape[1], k.shape[1], q.shape[2]
    lo = 0 if window is None else max(length - window, 0)
    out = []
    for h in range(heads):
        g = h // (heads // kv_heads)
        s = k[lo:length, g] @ q[0, h] / np.sqrt(dim)
        p = np.exp(s - s.max())
        out.append(p / p.sum() @ v[lo:length, g])
    return np.concatenate(out)


@pytest.mark.parametrize("window", [None, WINDOW], ids=["global", "window"])
def test_grouped_query_attention_matches_a_loop_over_heads(window):
    """Rows shorter than the window, at it and well past it (the ring has
    wrapped twice), unallocated entries holding huge values."""
    lengths = [7, 16, 17, 39]
    q, k, v, pool, table, pos = _paged_case(
        np.random.default_rng(3), lengths, window=window
    )
    got = paged_gqa_attention(
        jnp.asarray(q), pool, table, pos, block_size=BS, n_kv_heads=2,
        window=window,
    )
    for r, length in enumerate(lengths):
        np.testing.assert_allclose(
            got[r, 0], _per_head_loop(q[r], k[r], v[r], length, window), **TOL
        )


@pytest.mark.parametrize("window", [None, WINDOW], ids=["global", "window"])
def test_a_decode_step_reads_the_pool_in_place_as_the_gathered_form(
    window, monkeypatch
):
    """On the TPU a decode step is the kernel that walks each row's table
    (a ring turned to start at the window's first block, with the window's
    first key as a lower bound; a plain table through the form that reads
    shared leading blocks once: tests/test_shared_run_attention.py); here
    it runs interpreted."""
    from znicz_tpu.ops import attention as att

    lengths = [7, 16, 17, 39, 23]
    q, k, v, pool, table, pos = _paged_case(
        np.random.default_rng(21), lengths, window=window, n_blocks=48
    )
    live = jnp.asarray([7, 16, 17, 39, 0])  # the last row idles

    def attend():
        return np.asarray(paged_gqa_attention(
            jnp.asarray(q), pool, table, pos, block_size=BS, n_kv_heads=2,
            window=window, lengths=live,
        ))

    gathered = attend()
    assert int(att.paged_gqa_rows_read(
        table, live, block_size=BS, window=window
    )) == table.size * BS
    calls = []
    form = (
        "shared_run_decode_attention" if window is None
        else "latent_decode_attention"
    )
    kernel = getattr(att, form)
    monkeypatch.setattr(
        att, form, lambda *a, **kw: calls.append(kw) or kernel(*a, **kw),
    )
    monkeypatch.setattr(att, "_reads_pool_in_place", lambda tq: tq == 1)
    in_place = attend()
    assert len(calls) == 1 and ("starts" in calls[0]) == (window is not None)
    np.testing.assert_allclose(in_place, gathered, **TOL)
    assert not in_place[4].any()
    for r, length in enumerate(lengths[:4]):
        np.testing.assert_allclose(
            in_place[r, 0], _per_head_loop(q[r], k[r], v[r], length, window), **TOL
        )
    # what it read: each live row's keys from the first block it attends
    want = {None: 8 + 16 + 20 + 40, WINDOW: 8 + 16 + 20 + 20}[window]
    for count in (att.paged_gqa_rows_read, att.paged_gqa_rows_attended):
        assert int(count(table, live, block_size=BS, window=window)) == want


def test_a_decode_steps_idle_row_reads_nothing_and_gives_zeros():
    q, k, v, pool, table, pos = _paged_case(np.random.default_rng(4), [9, 21])
    got = paged_gqa_attention(
        jnp.asarray(q), pool, table, pos, block_size=BS, n_kv_heads=2,
        lengths=jnp.asarray([0, 21]),
    )
    assert not np.asarray(got[0]).any()
    np.testing.assert_allclose(
        got[1, 0], _per_head_loop(q[1], k[1], v[1], 21, None), **TOL
    )


@pytest.mark.parametrize(
    "behind, visible", [(WINDOW, False), (WINDOW - 1, True)],
    ids=["i-W", "i-W+1"],
)
def test_the_window_mask_at_its_edges(behind, visible):
    """One key is made to win any softmax it takes part in (its score is
    50 above the rest): the result is that key's value iff it is visible.
    Key ``i - W`` is not; key ``i - W + 1`` is."""
    rng = np.random.default_rng(5)
    length = 37
    q, k, v, pool, table, pos = _paged_case(rng, [length], window=WINDOW)
    at = length - 1 - behind
    # every head's query along one direction, the marked key along it too
    direction = np.ones(16, np.float32) / 4.0
    q[:] = direction * 8.0
    k[0, at] = direction * 25.0
    rows = np.asarray(gqa_cache_row(jnp.asarray(k), jnp.asarray(v)))
    # both keys lie in block 5, which the row still holds (key 21 is the
    # window's first): the mask is by index, not by what is allocated
    assert at // BS == (length - WINDOW) // BS == 5
    pool = np.array(pool)
    pool[int(table[0, 5]), at % BS] = rows[0, at]
    got = np.asarray(paged_gqa_attention(
        jnp.asarray(q), jnp.asarray(pool), table, pos, block_size=BS,
        n_kv_heads=2, window=WINDOW,
    ))[0, 0].reshape(4, 16)
    marked = np.repeat(v[0, at], 2, axis=0)  # head h reads K/V head h // 2
    assert np.allclose(got, marked, atol=1e-3) == visible


def test_ring_positions_name_the_newest_block_of_each_entry():
    pos = ring_key_positions(3, 4, jnp.asarray([1, 13, 22]))
    np.testing.assert_array_equal(pos[0, ::4] // 4, [0, -2, -1])
    np.testing.assert_array_equal(pos[1, ::4] // 4, [3, 1, 2])  # block 3 is newest
    np.testing.assert_array_equal(pos[2, ::4] // 4, [3, 4, 5])
    np.testing.assert_array_equal(pos[2, 4:8], [16, 17, 18, 19])


@pytest.mark.parametrize("normalize", [True, False])
def test_softmax_top_k_weights(normalize):
    rng = np.random.default_rng(6)
    h = rng.standard_normal((5, 64)).astype(np.float32)
    router = rng.standard_normal((64, 8)).astype(np.float32) / 8
    chosen, weight = moe.route_softmax_topk(
        jnp.asarray(h), jnp.asarray(router), top_k=3, normalize=normalize
    )
    logits = h @ router
    order = np.argsort(-logits, axis=-1)[:, :3]
    np.testing.assert_array_equal(chosen, order)
    top = np.take_along_axis(logits, order, axis=-1)
    over = top if normalize else logits
    want = np.exp(top) / np.exp(over).sum(-1, keepdims=True)
    np.testing.assert_allclose(weight, want, rtol=1e-5)
    if normalize:
        np.testing.assert_allclose(np.asarray(weight).sum(-1), 1.0, rtol=1e-6)


def test_relu_gated_experts_match_a_loop_with_one_expert_given_every_token(toy):
    """Expert 2 is every token's first choice and expert 5 nobody's."""
    rng = np.random.default_rng(7)
    block = toy.params[2]
    t = 11
    h = jnp.asarray(rng.standard_normal((t, 64)), jnp.float32)
    others = np.array([e for e in range(8) if e not in (2, 5)])
    chosen = np.stack(
        [np.full(t, 2)] + list(
            np.stack([rng.permutation(others)[:2] for _ in range(t)]).T
        ), axis=1,
    ).astype(np.int32)
    weight = rng.random((t, 3)).astype(np.float32)
    y, pairs = moe.held_experts_apply(
        h, jnp.asarray(chosen), jnp.asarray(weight), block["experts_gate"],
        block["experts_up"], block["experts_down"], first_expert=0,
        activation=jax.nn.relu,
    )
    want = np.zeros((t, 64), np.float32)
    for e in range(8):
        w_e = np.where(chosen == e, weight, 0.0).sum(-1)
        act = np.maximum(h @ block["experts_gate"][e], 0) * (h @ block["experts_up"][e])
        want += w_e[:, None] * np.asarray(act @ block["experts_down"][e])
    np.testing.assert_allclose(y, want, **TOL)
    assert int(pairs[2]) == t and int(pairs[5]) == 0 and int(pairs.sum()) == 3 * t
    # the gate's activation is the caller's: silu gives another result
    y_silu, _ = moe.held_experts_apply(
        h, jnp.asarray(chosen), jnp.asarray(weight), block["experts_gate"],
        block["experts_up"], block["experts_down"], first_expert=0,
    )
    assert float(jnp.max(jnp.abs(y_silu - y))) > 1e-2


def test_the_reference_returns_the_rows_asked_for(toy):
    seq = _tokens(np.random.default_rng(8), 21)
    whole = toy.reference_logits(seq)
    tail = toy.ref.logits(CFG, toy.w, list(seq), first_row=13, rows_pad_to=16)
    np.testing.assert_allclose(tail, whole[13:], rtol=1e-5, atol=1e-5)


def test_a_window_layer_is_rotary_and_a_global_layer_is_not(toy):
    """Short of the window the two kinds differ by the rotation alone: a
    reference that turns layer 0 too, or turns no layer, is far from the
    published one (which the tower matches, below), so the tolerance tells
    them apart."""
    seq = _tokens(np.random.default_rng(9), 12)
    want = toy.reference_logits(seq)
    for layout in ([1, 1, 1, 1], [0, 0, 0, 0]):
        other = toy.ref.logits(
            {**CFG, "name": str(layout), "rope_layout": layout,
             "sliding_window_layout": layout},
            toy.w, list(seq),
        )
        assert np.abs(np.asarray(other) - want).max() > 1e-2
    row = _Tables(toy)
    np.testing.assert_allclose(row.prefill(seq)[0], want[-1], **TOL)
    # the published pairing holds: from_config refuses layouts that differ
    with pytest.raises(ValueError, match="rope_layout"):
        window_lm.WindowGQAMoEModel.from_config(
            {**CFG, "rope_layout": [1, 1, 1, 1]}, max_positions=64
        )


# -- the tower through the two kinds of pool -------------------------------


class _Tables:
    """What the engine keeps for one row, by hand: a plain table for the
    global kind, a ring for the window kind, a fresh block an index, and
    the blocks behind the window given back (and poisoned, if asked)."""

    def __init__(self, toy, width=6, n_blocks=48):
        self.toy, self.width = toy, width
        self.pools = toy.model.init_pools(
            toy.params, {"global": n_blocks, "window": n_blocks}, BS
        )
        self.table = {
            "global": np.zeros(32, np.int32),
            "window": np.zeros(width, np.int32),
        }
        self.held = {}  # window kind: block index -> pool block
        self.next = 1
        self.released = []

    def ensure(self, first_pos, last_pos, poison=False):
        first = max(first_pos - WINDOW + 1, 0) // BS
        for blk in [b for b in self.held if b < first]:
            self.released.append(self.held.pop(blk))
            if poison:
                self._poison(self.released[-1])
        for blk in range(last_pos // BS + 1):
            if self.table["global"][blk] == 0:
                self.table["global"][blk] = self.next
                self.next += 1
            if blk >= first and blk not in self.held:
                self.held[blk] = self.table["window"][blk % self.width] = self.next
                self.next += 1

    def _poison(self, pid):
        for i, kind in enumerate(self.toy.model.layer_kinds):
            if kind == "window":
                self.pools[i] = {"kv": self.pools[i]["kv"].at[pid].set(1e4)}

    def tables(self):
        return {k: jnp.asarray(t) for k, t in self.table.items()}

    def prefill(self, prompt, poison=False):
        padded = -(-len(prompt) // BS) * BS
        tokens = np.zeros(padded, np.int32)
        tokens[: len(prompt)] = prompt
        for c in range(padded // BS):
            self.ensure(c * BS, (c + 1) * BS - 1, poison)
            last = (len(prompt) - 1) % BS if c == padded // BS - 1 else BS - 1
            self.pools, logits, _ = self.toy.model.prefill_chunk(
                self.toy.params, self.pools, self.tables(),
                jnp.asarray(tokens[None, c * BS:(c + 1) * BS]),
                jnp.int32(c * BS), block_size=BS, last=jnp.int32(last),
            )
        return logits

    def decode(self, token, pos, poison=False):
        self.ensure(pos, pos, poison)
        batch = {k: jnp.stack([t, jnp.zeros_like(t)]) for k, t in self.tables().items()}
        # a second, idle row rides along: it writes to the null block and
        # is routed nowhere
        self.pools, logits, load = self.toy.model.decode_step(
            self.toy.params, self.pools, batch, jnp.asarray([token, 0]),
            jnp.asarray([pos, 0]), block_size=BS,
            write_mask=jnp.asarray([True, False]),
        )
        return logits[0], load


@pytest.mark.parametrize(
    "n_prompt, n_total",
    [(9, 14), (27, 33), (13, 30)],
    ids=["under-the-window", "crosses-in-prefill", "crosses-while-decoding"],
)
def test_prefill_chunks_then_decode_steps_match_the_reference_forward(
    toy, n_prompt, n_total
):
    seq = _tokens(np.random.default_rng(10 + n_prompt), n_total)
    want = toy.reference_logits(seq)
    row = _Tables(toy)
    logits = row.prefill(seq[:n_prompt], poison=True)
    np.testing.assert_allclose(logits[0], want[n_prompt - 1], **TOL)
    for pos in range(n_prompt, n_total):
        logits, load = row.decode(seq[pos], pos, poison=True)
        np.testing.assert_allclose(logits, want[pos], **TOL)
        assert int(jnp.sum(load["pairs"])) == 4 * 3  # one live row, 4 layers
    # blocks behind the window were given back (and poisoned) as soon as
    # the row's next query could not see them
    assert bool(row.released) == (n_total > WINDOW + BS)


def test_decode_load_counts_the_rows_a_layer_of_each_kind_read(toy):
    row = _Tables(toy)
    seq = _tokens(np.random.default_rng(11), 9)
    row.prefill(seq[:8])
    _, load = row.decode(seq[8], 8)
    # off the TPU both kinds gather their tables whole, both rows
    assert int(load["cached_rows_by_kind"]["global"]) == 2 * 32 * BS
    assert int(load["cached_rows_by_kind"]["window"]) == 2 * 6 * BS
    for kind, rows in load["attended_rows_by_kind"].items():
        assert int(rows) == int(load["cached_rows_by_kind"][kind])
    assert int(load["cached_rows"]) == (2 * 32 * BS + 3 * 2 * 6 * BS) // 4


# -- through the engine ----------------------------------------------------


def test_the_engine_serves_the_reference_greedy_through_both_kinds(toy):
    rng = np.random.default_rng(12)
    eng = toy.engine()
    window = eng._kinds[1]
    assert [k.name for k in eng._kinds] == ["global", "window"]
    assert eng._kinds[0].width == 16 and window.width == 6
    # a window table is the window's blocks, the block being written and
    # the block a chunk of 4 steps can add: ceil((16 + 4 - 2) / 4) + 1
    assert eng._pools[0]["kv"].shape == (eng.n_blocks["global"], BS, 64)
    assert eng.block_bytes == 4 * BS * 64 * 4  # 4 layers of float32 rows
    assert window.block_bytes == 3 * BS * 64 * 4
    released0 = _counter("znicz_serve_window_blocks_released_total")
    ids = [
        eng.submit(_tokens(rng, n), new)
        for n, new in ((9, 5), (27, 8), (13, 19), (6, 30))
    ]
    eng.run()
    for rid in ids:
        completion = eng.completions[rid]
        assert completion.finish_reason in ("budget", "eos")
        assert toy.served_gaps(completion).max() < 1e-4
    assert _counter("znicz_serve_window_blocks_released_total") > released0
    # every block is back: both kinds, and the tables are empty
    for kind in eng._kinds:
        assert sorted(kind.free) == list(range(1, kind.n_blocks))
        assert not kind.tables.any()
    assert eng.stats()["kinds"]["window"]["blocks_released_behind_window"] > 0


def test_a_released_block_serves_another_row_and_the_first_rows_answer_holds(toy):
    """Row A crosses its window and gives blocks back; they are poisoned
    the moment they are free, B is admitted into them, and A still serves
    the reference's greedy tokens."""
    rng = np.random.default_rng(13)
    # a window pool so small that B can only start in what A gave back
    eng = toy.engine(n_blocks={"global": 40, "window": 9})
    window = eng._kinds[1]
    a = eng.submit(_tokens(rng, 26), 14)
    while not window.n_released:
        assert eng.tick()
    freed = set(window.free)
    given_back = sorted(freed - {b for b in range(1, 9) if window.ref[b]})
    assert given_back
    for i, kind in enumerate(toy.model.layer_kinds):
        if kind == "window":
            eng._pools[i] = {
                "kv": eng._pools[i]["kv"].at[jnp.asarray(sorted(freed))].set(1e4)
            }
    b = eng.submit(_tokens(rng, 7), 6)
    seen_by_b = set()
    while eng.tick():
        for slot, st in enumerate(eng._slots):
            if st is not None and st["req"].id == b:
                seen_by_b |= set(window.row_blocks[slot])
    assert seen_by_b & freed  # B ran in blocks A gave back
    for rid in (a, b):
        assert toy.served_gaps(eng.completions[rid]).max() < 1e-4


def test_preemption_and_readmission_of_a_row_beyond_its_window(toy):
    """Two rows that both outgrow a small global pool: the younger is
    preempted past its window and recomputed from its prompt."""
    rng = np.random.default_rng(14)
    eng = toy.engine(n_blocks={"global": 15, "window": 20})
    before = _counter("znicz_serve_preemptions_total")
    ids = [eng.submit(_tokens(rng, 21), 17), eng.submit(_tokens(rng, 19), 16)]
    eng.run()
    assert _counter("znicz_serve_preemptions_total") > before
    assert eng.stats()["preemptions"] >= 1
    for rid in ids:
        completion = eng.completions[rid]
        assert completion.n_new in (16, 17) or completion.finish_reason == "eos"
        assert toy.served_gaps(completion).max() < 1e-4
    for kind in eng._kinds:
        assert sorted(kind.free) == list(range(1, kind.n_blocks))


def test_admission_counts_what_a_request_holds_in_each_kind(toy):
    # 32 + 10 tokens: 11 global blocks, but never more than the 6 of a ring
    eng = toy.engine(n_blocks={"global": 12, "window": 7})
    eng.submit(_tokens(np.random.default_rng(15), 30), 10)
    with pytest.raises(RequestTooLargeError, match="kind 'global'"):
        toy.engine(n_blocks={"global": 11, "window": 7}).submit(
            _tokens(np.random.default_rng(15), 30), 10
        )
    with pytest.raises(RequestTooLargeError, match="kind 'window'"):
        toy.engine(n_blocks={"global": 12, "window": 6}).submit(
            _tokens(np.random.default_rng(15), 30), 10
        )
    with pytest.raises(ValueError, match="n_blocks by kind"):
        toy.engine(n_blocks=64)
    # a second long prompt waits until BOTH kinds can hold its prefill
    eng = toy.engine(n_blocks={"global": 40, "window": 8})
    rng = np.random.default_rng(16)
    ids = [eng.submit(_tokens(rng, 24), 4), eng.submit(_tokens(rng, 24), 4)]
    eng._admit_pending()
    assert eng.prefilling == 1 and eng.pending == 1
    eng.run()
    for rid in ids:
        assert toy.served_gaps(eng.completions[rid]).max() < 1e-4
    assert eng.stats()["preemptions"] == 0


def test_the_prefix_cache_is_off_unless_named_and_speculation_is_refused(toy):
    """Left to its default the prefix cache is off for a tower with a window
    kind, so nothing is published and nothing hashed; asked for by name it
    is served, a chain held a kind (tests/test_engine_prefix_kinds.py), and
    nothing is counted as refused.  Speculation stays refused by type."""
    before = _counter("znicz_serve_unsupported_total", feature="prefix_cache")
    assert toy.engine(prefix_cache=True).prefix_cache
    assert not toy.engine(prefix_cache=False).prefix_cache
    assert _counter(
        "znicz_serve_unsupported_total", feature="prefix_cache"
    ) == before
    before = _counter("znicz_serve_unsupported_total", feature="speculation")
    with pytest.raises(SpeculationUnsupportedError, match="WindowGQAMoEModel"):
        toy.engine(spec_k=2)
    assert _counter(
        "znicz_serve_unsupported_total", feature="speculation"
    ) == before + 1
    eng = toy.engine()
    assert eng.prefix_cache is False
    prompt = _tokens(np.random.default_rng(17), 26)
    hits = _counter("znicz_serve_prefix_hits_total")
    first = eng.submit(prompt, 5)
    eng.run()
    again = eng.submit(prompt, 5)
    eng.run()
    np.testing.assert_array_equal(
        eng.completions[again].tokens, eng.completions[first].tokens
    )
    assert _counter("znicz_serve_prefix_hits_total") == hits
    stats = eng.stats()["prefix_cache"]
    assert stats == {**stats, "enabled": False, "entries": 0, "hits": 0}
    assert not any(k.cache or k.block_hash or k.lru for k in eng._kinds)
    assert eng.prefix_probe(prompt)["cached_blocks"] == 0


def test_counters_and_gauges_by_kind(toy):
    rng = np.random.default_rng(18)
    eng = toy.engine()
    rows0 = {
        k: _counter("znicz_serve_decode_cached_rows_total", kind=k)
        for k in ("global", "window")
    }
    gathered0 = _counter("znicz_serve_decode_gathered_tokens_total")
    steps0 = _counter("znicz_serve_decode_steps_total")
    density0 = _series("znicz_serve_cache_bytes_per_resident_token")
    density0 = (density0[0]["sum"], density0[0]["count"]) if density0 else (0, 0)
    eng.submit(_tokens(rng, 30), 21)
    peak = {"global": 0, "window": 0}
    while eng.tick():
        for kind in eng._kinds:
            (gauge,) = _series("znicz_serve_pool_blocks_in_use", kind=kind.name)
            assert gauge["value"] == kind.referenced
            peak[kind.name] = max(peak[kind.name], kind.referenced)
    # 50 positions: the global kind holds them all (the row retires inside
    # the tick that allocates its 13th block), the window kind its ring
    assert peak == {"global": 12, "window": 6}
    steps = _counter("znicz_serve_decode_steps_total") - steps0
    rows = {
        k: _counter("znicz_serve_decode_cached_rows_total", kind=k) - rows0[k]
        for k in rows0
    }
    assert rows["window"] == steps * 2 * 6 * BS  # the ring, both slots
    assert rows["global"] > rows["window"]
    # the older counter keeps its meaning: a layer's mean (per chunk, the
    # device's integer mean of one global layer and three window layers)
    gathered = _counter("znicz_serve_decode_gathered_tokens_total") - gathered0
    assert abs(gathered - (rows["global"] + 3 * rows["window"]) / 4) <= steps
    (density,) = _series("znicz_serve_cache_bytes_per_resident_token")
    mean = (density["sum"] - density0[0]) / (density["count"] - density0[1])
    one_table = 4 * 64 * 4  # every layer's row for every token
    assert one_table / 2 < mean < one_table
    for phase in ("prefill", "decode"):
        assert _counter("znicz_serve_moe_layer_steps_total", phase=phase) > 0
    assert _counter("znicz_serve_moe_pairs_total", phase="decode", expert=0) >= 0


def test_window_release_is_a_trace_instant(toy):
    tracer = observability.get_tracer()
    was = tracer.recording
    tracer.start()
    try:
        eng = toy.engine()
        eng.submit(_tokens(np.random.default_rng(19), 30), 6)
        eng.run()
        events = [e for e in tracer.events() if e["name"] == "serve/window_release"]
    finally:
        if not was:
            tracer.stop()
    assert events and all(
        e["args"]["kind"] == "window" and e["args"]["blocks"] >= 1 for e in events
    )


# -- a tower of one kind is served as before -------------------------------


def test_a_one_kind_towers_tables_and_programs_are_unchanged():
    """The classic block: one kind, a plain table as wide as the longest
    row, the programs handed bare arrays under the same keys, and none of
    the by-kind series touched."""
    params = init_lm_params(64, 32, 2, 2, max_seq=64)
    rows0 = _counter("znicz_serve_decode_cached_rows_total")
    released0 = _counter("znicz_serve_window_blocks_released_total")
    eng = PagedDecodeEngine(
        params, n_heads=2, eos_id=0, batch_size=2, max_seq=64, block_size=BS,
        admit_every=4,
    )
    (kind,) = eng._kinds
    assert kind.window is None and kind.width == eng.blocks_per_row == 16
    assert eng.n_blocks == kind.n_blocks == 2 * 16 + 1
    assert eng.prefix_cache is True
    assert isinstance(eng._row_tables(0), jax.Array)
    assert eng._batch_tables(4).shape == (2, 4)
    pools = init_paged_kv(params, eng.n_blocks, BS)
    assert jax.tree_util.tree_structure(pools) == jax.tree_util.tree_structure(eng._pools)
    rng = np.random.default_rng(20)
    for n, new in ((3, 4), (11, 6), (20, 12)):  # 2, 4 and 8 blocks deep
        eng.submit(rng.integers(1, 64, n), new)
        eng.run()
    structure = (True, 0, False)
    assert set(eng.compile_stats()["programs"]) == {
        ("prefill", BS, structure),
        ("paged_chunk", 4, 2, 2, structure), ("paged_chunk", 4, 2, 4, structure),
        ("paged_chunk", 4, 2, 8, structure),
    }
    assert kind.row_base == [0, 0] and kind.n_released == 0
    assert _counter("znicz_serve_decode_cached_rows_total") == rows0
    assert _counter("znicz_serve_window_blocks_released_total") == released0
