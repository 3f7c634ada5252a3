"""The tower whose full layers keep a few keys a query, chosen by a learned
indexer, beside window layers of a second, wider latent attention
(workflow/sparse_latent_lm.py), and what it brought to ops/attention.py
(scores through the block table, exact selection, attention over the
selected rows, latent attention behind a ring) and to the engine (two
kinds of latent rows of different widths), against the equations of
``benchmarks/reference/dots3.py`` at a small size: 5 layers (full, full,
window, window, window: the leading dense layer and one whole period),
hidden 64, 4 heads of 16 + 8 over a latent of 16 in a full layer, 2 heads
of 24 + 8 over a latent of 32 in a window layer, an indexer of 4 heads of
16 that keeps 16 keys, a window of 9 keys at a block of 4, 16 routed
experts of which 8 are held, vocabulary 256, float32 weights, seeded.

ONE fixture (``toy``) builds the model, its parameters and the reference's
view of the same arrays.

Tolerances: the program and the reference compute the same float32 sums in
different orders (blocked, absorbed, through a cache), which moves a logit
of size ~1 by ~1e-5; 3e-4 holds that with room, and a wrong mask, a wrong
selection or a missing gate moves logits by 1e-1 and more."""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from znicz_tpu import observability
from znicz_tpu.ops import attention as att
from znicz_tpu.ops import moe
from znicz_tpu.services.engine import PagedDecodeEngine
from znicz_tpu.services.errors import (
    SpeculationUnsupportedError,
)
from znicz_tpu.workflow import sparse_latent_lm as slm

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BS = 4  # block size
WINDOW, TOP_K = 9, 16
TOL = dict(rtol=3e-4, atol=3e-4)
SIZES = dict(
    d_model=64, vocab=256, q_lora_rank=32, swa_q_lora_rank=32, v_head_dim=16,
    d_ff_dense=96, d_ff_expert=32, n_routed_experts=16,
)
CFG = {
    "name": "toy-dots3", "hidden_size": 64, "num_hidden_layers": 5,
    # the published pattern, longer than the layers run: the first 5 count
    "layer_types": ["full_attention", "full_attention"]
    + ["sliding_attention"] * 3 + ["full_attention"],
    "apply_mla_qkv_lora_rescale": True, "attention_gate_type": "headwise",
    "swa_attention_gate_type": "headwise", "num_attention_heads": 4,
    "q_lora_rank": 32, "kv_lora_rank": 16, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "rope_theta": 8e7,
    "swa_num_attention_heads": 2, "swa_q_lora_rank": 32,
    "swa_kv_lora_rank": 32, "swa_qk_nope_head_dim": 24,
    "swa_qk_rope_head_dim": 8, "swa_v_head_dim": 16, "swa_rope_theta": 5e4,
    "sliding_window_size": WINDOW, "index_n_heads": 4, "index_head_dim": 16,
    "index_topk": TOP_K, "n_routed_experts": 8, "num_experts_per_tok": 3,
    "routed_scaling_factor": 1.0, "norm_topk_prob": True,
    "scoring_func": "sigmoid", "topk_method": "noaux_tc",
    "rms_norm_eps": 1e-5, "rope_scaling": None, "vocab_size": 256,
    "deployment": {"first_expert": 4},
}


def _load_reference():
    spec = importlib.util.spec_from_file_location(
        "dots3_reference_for_tests",
        os.path.join(REPO, "benchmarks", "reference", "dots3.py"),
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    # its row blocks, at toy length
    module.BLOCK, module.QBLOCK, module.HEAD_GROUP = 16, 8, 2
    return module


def _reference_view(params):
    return {
        "embed": params[0]["embed"], "blocks": params[1:-1],
        "final_norm": params[-1]["final_norm"], "head": params[-1]["head"],
    }


class Toy:
    def __init__(self, first_expert=4, held=8, seed=1, max_positions=256):
        self.ref = _load_reference()
        self.cfg = {**CFG, "deployment": {"first_expert": first_expert}}
        self.model = slm.SparseLatentMoEModel.from_config(
            self.cfg, first_expert=first_expert, max_positions=max_positions
        )
        self.params = slm.init_params(
            self.model, held_experts=held, seed=seed, **SIZES
        )
        self.w = _reference_view(self.params)

    def reference_logits(self, tokens, **kwargs):
        return np.asarray(self.ref.logits(self.cfg, self.w, list(tokens), **kwargs))

    def engine(self, **kw):
        kw.setdefault("batch_size", 2)
        kw.setdefault("max_seq", 128)
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


def _counter(name, **labels):
    want = {k: str(v) for k, v in labels.items()}
    series = observability.get_registry().snapshot().get(name, {"series": []})
    return sum(
        s["value"] for s in series["series"]
        if want.items() <= {k: str(v) for k, v in s["labels"].items()}.items()
    )


# -- the small pieces ------------------------------------------------------


def test_the_model_reads_the_two_kinds_sizes_from_the_config(toy):
    m = toy.model
    assert m.full_layers == (True, True, False, False, False)
    assert m.layer_kinds == ("global", "global", "window", "window", "window")
    assert [(k.name, k.window) for k in m.cache_kinds] == [
        ("global", None), ("window", WINDOW)
    ]
    assert (m.full.n_heads, m.swa.n_heads) == (4, 2)
    assert m.full.softmax_scale == 24 ** -0.5 and m.swa.softmax_scale == 32 ** -0.5
    # the rescale after the norms: (hidden / rank) ** 0.5
    assert m.full.q_rescale == 2 ** 0.5 and m.full.kv_rescale == 2.0
    assert m.swa.q_rescale == m.swa.kv_rescale == 2 ** 0.5
    # whole 128-lane tiles of [c, k_r]; the indexer's keys have their pool
    assert m.row_widths == {"global": 128, "window": 128}
    with pytest.raises(ValueError, match="noaux_tc"):
        slm.SparseLatentMoEModel.from_config(
            {**CFG, "topk_method": "none"}, first_expert=0, max_positions=64
        )
    with pytest.raises(ValueError, match="head-wise"):
        slm.SparseLatentMoEModel.from_config(
            {**CFG, "attention_gate_type": "elementwise"}, first_expert=0,
            max_positions=64,
        )


def test_the_score_bias_chooses_and_the_unbiased_scores_weigh(toy):
    rng = np.random.default_rng(1)
    h = jnp.asarray(rng.standard_normal((9, 64)), jnp.float32)
    router = toy.params[2]["router"]
    bias = jnp.asarray(rng.standard_normal(16), jnp.float32)
    chosen, weight = moe.route_sigmoid_topk(h, router, top_k=3, bias=bias)
    scores = np.asarray(jax.nn.sigmoid(h @ router))
    order = np.argsort(-(scores + np.asarray(bias)), axis=-1)[:, :3]
    np.testing.assert_array_equal(chosen, order)
    top = np.take_along_axis(scores, order, axis=-1)
    np.testing.assert_allclose(weight, top / top.sum(-1, keepdims=True), rtol=1e-5)
    ref_idx, ref_w = toy.ref.route(toy.cfg, jnp.asarray(scores), bias)
    np.testing.assert_array_equal(chosen, ref_idx)
    np.testing.assert_allclose(weight, ref_w, rtol=1e-5)
    # the bias changed the choice, and no bias is the old router
    plain, _ = moe.route_sigmoid_topk(h, router, top_k=3)
    assert (np.asarray(plain) != np.asarray(chosen)).any()
    zero, zero_w = moe.route_sigmoid_topk(h, router, top_k=3, bias=jnp.zeros(16))
    np.testing.assert_array_equal(zero, plain)


def _index_case(rng, lengths, *, j=4, d_idx=16, n_blocks=40):
    """Rows of ``lengths`` cached tokens whose indexer keys sit in a pool,
    through plain tables; unallocated blocks hold huge values.  The last
    token of each row is the query's."""
    b, t_max = len(lengths), max(lengths)
    keys = rng.standard_normal((b, t_max, d_idx)).astype(np.float32)
    q = rng.standard_normal((b, 1, j, d_idx)).astype(np.float32)
    w = rng.standard_normal((b, 1, j)).astype(np.float32)
    pool = np.full((n_blocks, BS, d_idx), 1e4, np.float32)
    m = -(-t_max // BS)
    table = np.zeros((b, m), np.int32)
    free = iter(rng.permutation(np.arange(1, n_blocks)))
    for r, length in enumerate(lengths):
        for blk in range(-(-length // BS)):
            table[r, blk] = pid = next(free)
            for o in range(BS):
                if blk * BS + o < length:
                    pool[pid, o] = keys[r, blk * BS + o]
    pos = np.asarray(lengths)[:, None] - 1
    return q, w, keys, jnp.asarray(pool), jnp.asarray(table), jnp.asarray(pos)


def test_index_scores_through_the_table_match_a_loop_over_heads(monkeypatch):
    """Rows of one block, of many and of more than one chunk of table
    entries; keys past a row's query score -inf."""
    monkeypatch.setattr(att, "INDEX_CHUNK_BLOCKS", 4)
    lengths = [3, 17, 70, 33]
    q, w, keys, pool, table, pos = _index_case(np.random.default_rng(2), lengths)
    got = np.asarray(att.paged_index_scores(
        jnp.asarray(q), jnp.asarray(w), pool, table, pos, block_size=BS
    ))
    assert got.shape == (4, 1, table.shape[1] * BS)
    for r, length in enumerate(lengths):
        want = (np.maximum(keys[r, :length] @ q[r, 0].T, 0) * w[r, 0]).sum(-1)
        np.testing.assert_allclose(got[r, 0, :length], want, **TOL)
        assert np.isneginf(got[r, 0, length:]).all()
    # a decode step's idle row scores nothing
    live = jnp.asarray([3, 0, 70, 33])

    def decode_scores():
        return np.asarray(att.paged_index_scores(
            jnp.asarray(q), jnp.asarray(w), pool, table, pos, block_size=BS,
            lengths=live,
        ))

    idle = decode_scores()
    assert np.isneginf(idle[1]).all() and np.isfinite(idle[2, 0, :70]).all()
    # on the TPU a decode step's keys are read in place by the kernel
    # (interpreted here): the same scores, the same mask
    calls = []
    kernel = att.index_decode_scores
    monkeypatch.setattr(
        att, "index_decode_scores",
        lambda *a: calls.append(a) or kernel(*a),
    )
    monkeypatch.setattr(att, "_reads_pool_in_place", lambda tq: tq == 1)
    in_place = decode_scores()
    assert len(calls) == 1
    np.testing.assert_array_equal(np.isneginf(in_place), np.isneginf(idle))
    finite = np.isfinite(idle)
    np.testing.assert_allclose(in_place[finite], idle[finite], **TOL)


def test_selection_is_exact_and_keeps_every_key_of_a_short_row():
    rng = np.random.default_rng(3)
    scores = rng.standard_normal((2, 3, 40)).astype(np.float32)
    scores[0, :, 10:] = -np.inf  # ten keys visible: fewer than top_k
    scores[1, 1, 7] = scores[1, 1, 30] = np.sort(scores[1, 1])[-TOP_K]  # a tie at the cut
    scores[1, 2, :20] = 0.0  # many equal scores, zeros and negatives about
    keep = np.asarray(att.select_top_keys(jnp.asarray(scores), TOP_K))
    assert keep[0, :, :10].all() and not keep[0, :, 10:].any()
    for t in range(3):
        # of equal scores the earlier key: a stable sort's first top_k
        want = np.argsort(-scores[1, t], kind="stable")[:TOP_K]
        assert set(np.flatnonzero(keep[1, t])) == set(want), t
    assert keep[1, 1, 7] and not keep[1, 1, 30]
    # it is jax.lax.top_k's choice
    _, idx = jax.lax.top_k(jnp.asarray(scores[1]), TOP_K)
    for t in range(3):
        assert set(np.flatnonzero(keep[1, t])) == set(np.asarray(idx[t]).tolist())


ROWS = att.SELECT_TILE_ROWS
N_SLOTS = 2 * ROWS + 3  # whole tiles and a part of one


def _whole_batch_selection(scores, lengths, top_k):
    """``select_live_rows`` as it was before PR 45: every row of the batch,
    live or idle, through ``select_top_keys``."""
    return att._select_every_row(scores, top_k)


@pytest.mark.parametrize(
    "live", [0, 1, ROWS - 1, ROWS, ROWS + 1, N_SLOTS],
    ids=["none", "one", "tile-less-one", "tile", "tile-and-one", "all"],
)
def test_the_live_rows_keep_what_the_whole_batch_keeps(live):
    """A decode step's mask, chosen a tile of its live rows at a time, is
    EQUAL to the selection over every slot: live rows scattered among idle
    ones, a row that sees fewer keys than are kept, ties at the cut;
    nothing kept for an idle row."""
    rng = np.random.default_rng(70 + live)
    n_keys = 40
    lengths = np.zeros(N_SLOTS, np.int32)
    where = rng.permutation(N_SLOTS)[:live]
    lengths[where] = rng.integers(TOP_K, n_keys + 1, live)
    lengths[where[:1]] = TOP_K - 5
    lengths[where[1:2]] = n_keys
    values = rng.integers(0, 4, (N_SLOTS, 1, n_keys)).astype(np.float32)
    scores = jnp.asarray(np.where(
        np.arange(n_keys) < lengths[:, None, None], values, -np.inf
    ))
    want, want_scored, want_selected, _ = _whole_batch_selection(
        scores, jnp.asarray(lengths), TOP_K
    )
    keep, scored, selected, visited = att.select_live_rows(
        scores, jnp.asarray(lengths), TOP_K
    )
    assert keep.shape == want.shape and keep.dtype == want.dtype
    np.testing.assert_array_equal(keep, want)
    assert not np.asarray(keep)[lengths == 0].any()
    assert np.asarray(keep)[:, 0].sum(axis=-1).tolist() == np.minimum(lengths, TOP_K).tolist()
    assert int(scored) == int(want_scored) == lengths.sum()
    assert int(selected) == int(want_selected)
    assert int(visited) == -(-live // ROWS) * ROWS
    if live > 1:  # the cut of the row that sees every key is a tie
        row = np.sort(values[lengths.argmax(), 0])[::-1]
        assert row[TOP_K - 1] == row[TOP_K]


def test_the_selection_of_live_rows_is_a_decode_steps():
    with pytest.raises(ValueError, match="decode step"):
        att.select_live_rows(jnp.zeros((2, 3, 8)), jnp.ones(2, jnp.int32), 4)


def _latent_case(rng, lengths, sizes, *, width, ring=None, n_blocks=48):
    """Latent rows ``[c, k_r, zeros]`` of ``lengths`` tokens in a pool of
    ``width`` lanes, through plain tables or rings of ``ring`` entries."""
    b, t_max = len(lengths), max(lengths)
    h, dc, dn, dr = sizes
    c = rng.standard_normal((b, t_max, dc)).astype(np.float32)
    k_r = rng.standard_normal((b, t_max, dr)).astype(np.float32)
    q_nope = rng.standard_normal((b, 1, h, dn)).astype(np.float32)
    q_rope = rng.standard_normal((b, 1, h, dr)).astype(np.float32)
    wk_b = rng.standard_normal((dc, h * dn)).astype(np.float32) / 4
    wv_b = rng.standard_normal((dc, h * 16)).astype(np.float32) / 4
    pool = np.zeros((n_blocks, BS, width), np.float32)
    m = ring or -(-t_max // BS)
    table = np.zeros((b, m), np.int32)
    free = iter(rng.permutation(np.arange(1, n_blocks)))
    for r, length in enumerate(lengths):
        first = 0 if ring is None else max(length - WINDOW, 0) // BS
        for blk in range(first, -(-length // BS)):
            table[r, blk % m] = pid = next(free)
            for o in range(BS):
                if blk * BS + o < length:
                    pool[pid, o, :dc] = c[r, blk * BS + o]
                    pool[pid, o, dc:dc + dr] = k_r[r, blk * BS + o]
    pos = np.asarray(lengths)[:, None] - 1
    return (q_nope, q_rope, c, k_r, wk_b, wv_b, jnp.asarray(pool),
            jnp.asarray(table), jnp.asarray(pos))


def _latent_loop(q_nope, q_rope, c, k_r, wk_b, wv_b, keys, scale):
    """Per head, K and V materialised, over the key positions ``keys``."""
    h, dn = q_nope.shape
    k = (c[keys] @ wk_b).reshape(len(keys), h, dn)
    v = (c[keys] @ wv_b).reshape(len(keys), h, -1)
    out = []
    for head in range(h):
        s = (k[:, head] @ q_nope[head] + k_r[keys] @ q_rope[head]) * scale
        p = np.exp(s - s.max())
        out.append(p / p.sum() @ v[:, head])
    return np.concatenate(out)


@pytest.mark.parametrize("in_place", [False, True], ids=["chunked", "kernel"])
def test_attention_over_the_kept_keys_matches_a_loop_over_heads(
    in_place, monkeypatch
):
    """Rows of one block, of many, of more than one chunk of the table and
    an idle one; walking the table a chunk at a time with a running
    softmax, and by the kernel under its mask (interpreted here)."""
    monkeypatch.setattr(att, "INDEX_CHUNK_BLOCKS", 4)
    rng = np.random.default_rng(4)
    lengths = [7, 40, 25, 12]
    q_nope, q_rope, c, k_r, wk_b, wv_b, pool, table, pos = _latent_case(
        rng, lengths, (4, 16, 16, 8), width=128
    )
    picked = [np.sort(rng.permutation(n)[: min(n, TOP_K)]) for n in lengths]
    picked[1] = picked[1][picked[1] >= 16]  # a whole chunk with no key kept
    keep = np.zeros((4, 1, table.shape[1] * BS), bool)
    for r, p in enumerate(picked):
        keep[r, 0, p] = True
    live = jnp.asarray([7, 40, 25, 0])
    if in_place:
        monkeypatch.setattr(att, "_reads_pool_in_place", lambda tq: tq == 1)

    def attend(keep):
        return np.asarray(att.kept_latent_attention(
            jnp.asarray(q_nope), jnp.asarray(q_rope), pool, table, pos,
            jnp.asarray(keep), jnp.asarray(wk_b), jnp.asarray(wv_b),
            block_size=BS, scale=24 ** -0.5, lengths=live,
        ))

    got = attend(keep)
    assert not got[3].any()
    for r, p in enumerate(picked[:3]):
        want = _latent_loop(
            q_nope[r, 0], q_rope[r, 0], c[r], k_r[r], wk_b, wv_b, p, 24 ** -0.5
        )
        np.testing.assert_allclose(got[r, 0], want, **TOL)
    # a query with no key kept gives zeros, not NaN
    assert not attend(np.zeros_like(keep)).any()


@pytest.mark.parametrize("in_place", [False, True], ids=["gathered", "kernel"])
def test_window_latent_attention_matches_a_loop_over_heads(in_place, monkeypatch):
    """Rows short of the window, at its edge and well past it (the ring
    has wrapped), one idle; gathered, and by the kernel that walks the
    ring turned to the window's first block (interpreted here)."""
    lengths = [5, 9, 10, 39, 23]
    q_nope, q_rope, c, k_r, wk_b, wv_b, pool, table, pos = _latent_case(
        np.random.default_rng(5), lengths, (2, 32, 24, 8), width=128, ring=5
    )
    live = jnp.asarray([5, 9, 10, 39, 0])
    if in_place:
        monkeypatch.setattr(att, "_reads_pool_in_place", lambda tq: tq == 1)
    got = np.asarray(att.paged_window_latent_attention(
        jnp.asarray(q_nope), jnp.asarray(q_rope), pool, table, pos,
        jnp.asarray(wk_b), jnp.asarray(wv_b), block_size=BS,
        scale=32 ** -0.5, window=WINDOW, lengths=live,
    ))
    assert not got[4].any()
    for r, length in enumerate(lengths[:4]):
        keys = np.arange(max(length - WINDOW, 0), length)
        want = _latent_loop(
            q_nope[r, 0], q_rope[r, 0], c[r], k_r[r], wk_b, wv_b, keys,
            32 ** -0.5,
        )
        np.testing.assert_allclose(got[r, 0], want, **TOL)


# -- the tower through the two kinds of pool -------------------------------


class _Tables:
    """What the engine keeps for one row, by hand: a plain table for the
    global kind, a ring for the window kind, a fresh block an index, and
    the window kind's blocks behind the window given back and poisoned."""

    def __init__(self, toy, width=5, n_blocks=64):
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
        self.selected = []  # the keys layer 0 kept, a call

    def ensure(self, first_pos, last_pos):
        first = max(first_pos - WINDOW + 1, 0) // BS
        for blk in [b for b in self.held if b < first]:
            pid = self.held.pop(blk)
            for i, kind in enumerate(self.toy.model.layer_kinds):
                if kind == "window":
                    self.pools[i] = {"kv": self.pools[i]["kv"].at[pid].set(1e4)}  # no "idx" there
        for blk in range(last_pos // BS + 1):
            if self.table["global"][blk] == 0:
                self.table["global"][blk] = self.next
                self.next += 1
            if blk >= first and blk not in self.held:
                self.held[blk] = self.table["window"][blk % self.width] = self.next
                self.next += 1

    def tables(self):
        return {k: jnp.asarray(t) for k, t in self.table.items()}

    def prefill(self, prompt):
        padded = -(-len(prompt) // BS) * BS
        tokens = np.zeros(padded, np.int32)
        tokens[: len(prompt)] = prompt
        for c in range(padded // BS):
            self.ensure(c * BS, (c + 1) * BS - 1)
            last = (len(prompt) - 1) % BS if c == padded // BS - 1 else BS - 1
            self.pools, logits, load = self.toy.model.prefill_chunk(
                self.toy.params, self.pools, self.tables(),
                jnp.asarray(tokens[None, c * BS:(c + 1) * BS]),
                jnp.int32(c * BS), block_size=BS, last=jnp.int32(last),
            )
        return logits, load

    def decode(self, token, pos):
        self.ensure(pos, pos)
        batch = {k: jnp.stack([t, jnp.zeros_like(t)]) for k, t in self.tables().items()}
        # a second, idle row rides along: it writes to the null block,
        # scores no key and is routed nowhere
        self.pools, logits, load = self.toy.model.decode_step(
            self.toy.params, self.pools, batch, jnp.asarray([token, 0]),
            jnp.asarray([pos, 0]), block_size=BS,
            write_mask=jnp.asarray([True, False]),
        )
        return logits[0], load


@pytest.mark.parametrize(
    "n_prompt, n_total",
    [(7, 13), (11, 30), (50, 75), (21, 27)],
    ids=["under-top-k-and-window", "crosses-both-while-decoding",
         "far-past-top-k", "crosses-top-k-in-prefill"],
)
def test_prefill_chunks_then_decode_steps_match_the_reference_forward(
    toy, n_prompt, n_total
):
    seq = _tokens(np.random.default_rng(10 + n_prompt), n_total)
    want = toy.reference_logits(seq)
    row = _Tables(toy)
    logits, load = row.prefill(seq[:n_prompt])
    np.testing.assert_allclose(logits[0], want[n_prompt - 1], **TOL)
    # the last chunk's queries, a full layer: every key up to each scored,
    # no more than top_k kept
    first = (n_prompt - 1) // BS * BS
    assert int(load["sparse_scored"]) == sum(range(first + 1, first + BS + 1))
    assert int(load["sparse_selected"]) == sum(
        min(n, TOP_K) for n in range(first + 1, first + BS + 1)
    )
    for pos in range(n_prompt, n_total):
        logits, load = row.decode(seq[pos], pos)
        np.testing.assert_allclose(logits, want[pos], **TOL)
        assert int(jnp.sum(load["pairs"])) <= 4 * 3  # one live row, 4 routed layers
        assert int(load["sparse_scored"]) == pos + 1
        assert int(load["sparse_selected"]) == min(pos + 1, TOP_K)
        # off the TPU a full layer gathers its table whole, both rows
        assert int(load["cached_rows_by_kind"]["global"]) == 2 * 32 * BS


def test_the_selection_matters_and_each_control_moves_the_logits(toy):
    """Past top-k the selected keys are not the recent ones nor all: a
    reference that selects otherwise is far from the published one, which
    the tower matches (above)."""
    seq = _tokens(np.random.default_rng(20), 60)
    want = toy.reference_logits(seq, first_row=40)
    for control in (
        {"select": "all"}, {"select": "recent"}, {"index_topk": TOP_K // 2},
    ):
        other = toy.reference_logits(seq, first_row=40, **control)
        assert np.abs(other - want).max() > 5e-2, control
    # under top-k keys every rule keeps them all
    short = seq[: TOP_K - 2]
    np.testing.assert_allclose(
        toy.reference_logits(short, select="recent"),
        toy.reference_logits(short), rtol=1e-6,
    )


def test_in_float32_the_program_selects_the_keys_the_reference_selects(
    toy, monkeypatch
):
    """Layer 0's selection, every query of a 45-token prompt: the same
    sets (the reference's as a mask, the program's as positions)."""
    seq = _tokens(np.random.default_rng(21), 45)
    ref, cfg, b = toy.ref, toy.cfg, toy.w["blocks"][0]
    s = ref.sizes_of(cfg, True)
    freqs = ref.inv_freq(s["d_r"], s["theta"])
    x = toy.w["embed"][jnp.asarray(seq)]
    pos = jnp.arange(len(seq))

    def mm(a, w):
        return a @ w

    u, c_q, _, _ = ref._queries(cfg, b, s, x, pos, mm, freqs)
    k_i = ref.index_keys(cfg, b, u, pos, mm, freqs)
    scores = ref.index_scores(cfg, b, u, c_q, pos, k_i, mm, ref._identity, freqs)
    mask = np.asarray(ref.selection(scores, pos, pos, "score", TOP_K))

    picked = []
    real = att.select_top_keys

    def recording(scores, top_k):
        keep = real(scores, top_k)
        picked.append(np.asarray(keep))
        return keep

    monkeypatch.setattr(att, "select_top_keys", recording)
    _Tables(toy).prefill(seq)
    # two full layers a chunk: layer 0's call comes first
    layer0 = picked[::2]
    assert len(layer0) == -(-len(seq) // BS)
    for chunk, keep in enumerate(layer0):
        for i in range(BS):
            t = chunk * BS + i
            if t < len(seq):
                np.testing.assert_array_equal(
                    keep[0, i, : len(seq)], mask[t], err_msg=str(t)
                )
                assert not keep[0, i, len(seq):].any()


def test_a_decode_step_by_the_kernel_agrees_with_the_gathered_form(
    toy, monkeypatch
):
    """With the indexer's keys, the full layers' rows under their mask and
    the window layers' rows read in place (the two kernels, interpreted) a
    decode step gives what the gathered forms give, and counts both kinds'
    rows in whole blocks."""
    seq = _tokens(np.random.default_rng(22), 31)
    rows = [_Tables(toy), _Tables(toy)]
    for row in rows:
        row.prefill(seq[:30])
    gathered, load_g = rows[0].decode(seq[30], 30)
    monkeypatch.setattr(att, "_reads_pool_in_place", lambda tq: tq == 1)
    in_place, load_k = rows[1].decode(seq[30], 30)
    np.testing.assert_allclose(in_place, gathered, **TOL)
    assert int(load_g["cached_rows_by_kind"]["window"]) == 2 * 5 * BS
    # keys 22..30 of the live row: blocks 5, 6 and 7; all 31 in a full layer
    assert int(load_k["cached_rows_by_kind"]["window"]) == 3 * BS
    assert int(load_k["cached_rows_by_kind"]["global"]) == 8 * BS
    assert int(load_k["sparse_selected"]) == int(load_g["sparse_selected"]) == TOP_K


@pytest.mark.parametrize("live", [1, ROWS + 1], ids=["one", "tile-and-one"])
def test_a_decode_step_is_the_step_that_selected_over_every_slot(
    toy, live, monkeypatch
):
    """The tower's step with the selection tiled over its live rows gives
    the logits, the pools and the sums of the step that selected over the
    whole batch, to the bit (pools of random rows, the live rows scattered
    at random positions)."""
    rng = np.random.default_rng(80 + live)
    n_slots, m, ring = ROWS + 3, 16, 5
    blocks = n_slots * m + 1
    pools = jax.tree.map(
        lambda p: jnp.asarray(rng.standard_normal(p.shape), p.dtype),
        toy.model.init_pools(toy.params, {"global": blocks, "window": blocks}, BS),
    )
    table = 1 + rng.permutation(n_slots * m).reshape(n_slots, m)
    tables = {
        "global": jnp.asarray(table, jnp.int32),
        "window": jnp.asarray(table[:, :ring], jnp.int32),
    }
    mask = np.zeros(n_slots, bool)
    mask[rng.permutation(n_slots)[:live]] = True
    pos = jnp.asarray(np.where(mask, rng.integers(0, m * BS, n_slots), 0), jnp.int32)
    tokens = jnp.asarray(_tokens(rng, n_slots))

    def step():
        # a new function: a new trace, whatever is patched
        return jax.jit(
            lambda *a, **kw: toy.model.decode_step(*a, **kw),
            static_argnames=("block_size",),
        )(
            toy.params, pools, tables, tokens, pos, block_size=BS,
            write_mask=jnp.asarray(mask),
        )

    got_pools, logits, load = step()
    monkeypatch.setattr(att, "select_live_rows", _whole_batch_selection)
    want_pools, want_logits, want_load = step()
    np.testing.assert_array_equal(logits, want_logits)
    jax.tree.map(np.testing.assert_array_equal, got_pools, want_pools)
    assert int(load.pop("sparse_rows")) == -(-live // ROWS) * ROWS
    assert int(want_load.pop("sparse_rows")) == n_slots
    jax.tree.map(np.testing.assert_array_equal, load, want_load)


# -- through the engine ----------------------------------------------------


def test_the_engine_serves_the_reference_greedy_through_both_kinds(toy):
    rng = np.random.default_rng(12)
    eng = toy.engine()
    full, window = eng._kinds
    assert (full.name, window.name) == ("global", "window")
    # a window table: ceil((9 + 4 - 2) / 4) + 1 entries
    assert full.width == 32 and window.width == 4
    # a width a kind: a full layer's latent row and, beside it, its
    # indexer key; a window layer's latent row
    assert eng._pools[0]["kv"].shape == (eng.n_blocks["global"], BS, 128)
    assert eng._pools[0]["idx"].shape == (eng.n_blocks["global"], BS, 16)
    assert eng._pools[2]["kv"].shape == (eng.n_blocks["window"], BS, 128)
    assert set(eng._pools[2]) == {"kv"}
    assert full.block_bytes == 2 * BS * (128 + 16) * 4
    assert window.block_bytes == 3 * BS * 128 * 4
    assert eng.block_bytes == full.block_bytes + window.block_bytes
    scored0 = {
        p: _counter("znicz_serve_sparse_keys_scored_total", phase=p)
        for p in ("prefill", "decode")
    }
    selected0 = _counter("znicz_serve_sparse_keys_selected_total", phase="decode")
    rows0 = _counter("znicz_serve_decode_cached_rows_total", kind="global")
    ids = [
        eng.submit(_tokens(rng, n), new)
        for n, new in ((9, 5), (70, 12), (13, 19), (33, 30))
    ]
    eng.run()
    for rid in ids:
        completion = eng.completions[rid]
        assert completion.finish_reason in ("budget", "eos")
        assert toy.served_gaps(completion).max() < 1e-4
    for phase in ("prefill", "decode"):
        assert _counter(
            "znicz_serve_sparse_keys_scored_total", phase=phase
        ) > scored0[phase]
    selected = _counter(
        "znicz_serve_sparse_keys_selected_total", phase="decode"
    ) - selected0
    assert 0 < selected < _counter(
        "znicz_serve_decode_cached_rows_total", kind="global"
    ) - rows0
    for kind in eng._kinds:
        assert sorted(kind.free) == list(range(1, kind.n_blocks))
        assert not kind.tables.any()
    assert eng.stats()["kinds"]["window"]["blocks_released_behind_window"] > 0


def test_a_preempted_row_is_readmitted_and_still_serves_the_reference(toy):
    """A global pool too small for both rows: the younger is preempted,
    recomputed from its prompt and finishes with the reference's greedy."""
    rng = np.random.default_rng(13)
    eng = toy.engine(n_blocks={"global": 24, "window": 16})
    before = _counter("znicz_serve_preemptions_total")
    ids = [eng.submit(_tokens(rng, n), 40) for n in (38, 30)]
    eng.run()
    assert _counter("znicz_serve_preemptions_total") > before
    for rid in ids:
        completion = eng.completions[rid]
        assert completion.finish_reason in ("budget", "eos")
        assert toy.served_gaps(completion).max() < 1e-4


def test_what_the_tower_is_not_served_with_is_refused_by_name(toy):
    before = {
        f: _counter("znicz_serve_unsupported_total", feature=f)
        for f in ("speculation", "prefix_cache")
    }
    with pytest.raises(SpeculationUnsupportedError):
        toy.engine(spec_k=2)
    # the prefix cache is off by default and served when named (the engine
    # decides from the kinds' windows: tests/test_engine_prefix_kinds.py)
    assert toy.engine(prefix_cache=True).prefix_cache
    with pytest.raises(ValueError, match="by kind"):
        toy.engine(n_blocks=64)
    for feature, n in before.items():
        assert _counter("znicz_serve_unsupported_total", feature=feature) == n + (
            feature == "speculation"
        )
