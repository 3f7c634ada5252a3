"""The latent-attention, routed-experts tower (workflow/latent_lm.py) and
what it brought to ops/ and services/engine.py, against the equations of
``benchmarks/reference/axk1.py`` at a small size: 4 layers (one dense,
three routed), hidden 64, 16 experts of which 4 are held, 4 heads, latent
16 + rope 8, vocabulary 256, float32 weights, seeded.

ONE fixture (``toy``) builds the model, its parameters and the reference's
view of the same arrays, so the program and the reference cannot drift
apart in a test of their own."""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from znicz_tpu import observability
from znicz_tpu.ops import moe, rope
from znicz_tpu.ops.attention import paged_latent_attention
from znicz_tpu.ops.normalization import rms_norm
from znicz_tpu.services.engine import PagedDecodeEngine
from znicz_tpu.services.errors import SpeculationUnsupportedError
from znicz_tpu.workflow import latent_lm, paged_tower
from znicz_tpu.workflow.generate import copy_paged_block

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BS = 8  # block size
SIZES = dict(
    d_model=64, n_layers=4, vocab=256, q_lora_rank=24, v_head_dim=8,
    d_ff_dense=96, d_ff_expert=32, n_routed_experts=16,
)


def _load_reference():
    spec = importlib.util.spec_from_file_location(
        "axk1_reference_for_tests",
        os.path.join(REPO, "benchmarks", "reference", "axk1.py"),
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    module.BLOCK = 8  # its row blocks, at toy length
    return module


def _config(first_expert, held):
    """The keys of the published config.json that the reference and
    ``LatentMoEModel.from_config`` read, at toy size."""
    return {
        "name": f"toy-axk1-{first_expert}-{held}",
        "hidden_size": 64, "num_attention_heads": 4, "q_lora_rank": 24,
        "kv_lora_rank": 16, "qk_nope_head_dim": 8, "qk_rope_head_dim": 8,
        "v_head_dim": 8, "num_experts_per_tok": 4, "n_routed_experts": held,
        "routed_scaling_factor": 2.5, "norm_topk_prob": True,
        "rms_norm_eps": 1e-6, "rope_theta": 10000, "topk_method": "none",
        "rope_scaling": {
            "beta_fast": 32, "beta_slow": 1, "factor": 32, "mscale": 1,
            "mscale_all_dim": 1, "original_max_position_embeddings": 16,
            "type": "yarn",
        },
        "deployment": {"first_expert": first_expert},
    }


def _reference_view(params):
    return {
        "embed": params[0]["embed"], "blocks": params[1:-1],
        "final_norm": params[-1]["final_norm"], "head": params[-1]["head"],
    }


class Toy:
    def __init__(self, first_expert=4, held=4, seed=1, max_positions=256):
        self.ref = _load_reference()
        self.cfg = _config(first_expert, held)
        self.model = latent_lm.LatentMoEModel.from_config(
            self.cfg, first_expert=first_expert, max_positions=max_positions
        )
        self.params = latent_lm.init_params(
            self.model, held_experts=held, seed=seed, **SIZES
        )
        self.w = _reference_view(self.params)

    def reference_logits(self, tokens):
        return np.asarray(self.ref.logits(self.cfg, self.w, list(tokens)))

    def engine(self, **kw):
        kw.setdefault("batch_size", 4)
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
        logits = self.ref.logits(self.cfg, self.w, seq[:-1])
        return np.asarray(
            self.ref.served_gaps(logits, n_prompt, seq[n_prompt:])
        )


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


def test_yarn_frequencies_and_softmax_scale_match_the_reference(toy):
    np.testing.assert_allclose(
        toy.model._inv_freq(), toy.ref.yarn_inv_freq(toy.cfg), rtol=1e-6
    )
    assert toy.model.softmax_scale == pytest.approx(
        toy.ref.softmax_scale(toy.cfg), rel=1e-7
    )
    # factor 32 stretches the slow pairs and leaves the fast ones
    plain = 1.0 / 10000 ** (np.arange(0, 8, 2) / 8)
    got = np.asarray(toy.model._inv_freq())
    assert got[0] == pytest.approx(plain[0]) and got[-1] == pytest.approx(plain[-1] / 32)


def test_rotary_and_rms_norm_match_the_reference(toy):
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.standard_normal((5, 3, 8)), jnp.float32)
    pos = jnp.asarray([0, 1, 7, 40, 200])
    inv_freq = toy.ref.yarn_inv_freq(toy.cfg)
    np.testing.assert_allclose(
        rope.apply_rotary(a, pos, inv_freq), toy.ref.rot(a, pos, inv_freq),
        atol=1e-6,
    )
    gain = jnp.asarray(rng.standard_normal(8), jnp.float32)
    np.testing.assert_allclose(
        rms_norm(a, gain, eps=1e-6), toy.ref.rms(a, gain, 1e-6), atol=1e-6
    )


def test_sigmoid_router_matches_the_reference(toy):
    rng = np.random.default_rng(1)
    h = jnp.asarray(rng.standard_normal((9, 64)), jnp.float32)
    router = toy.params[2]["router"]
    chosen, weight = moe.route_sigmoid_topk(h, router, top_k=4, scale=2.5)
    ref_idx, ref_w = toy.ref.route(toy.cfg, jax.nn.sigmoid(h @ router))
    np.testing.assert_array_equal(chosen, ref_idx)
    np.testing.assert_allclose(weight, ref_w, rtol=1e-5)
    np.testing.assert_allclose(np.sum(weight, axis=-1), 2.5, rtol=1e-5)


@pytest.mark.parametrize("tq", [1, 3])
def test_absorbed_attention_equals_materialised_on_the_same_cache(tq):
    rng = np.random.default_rng(2)
    b, h, dn, dr, dc, dv, n_blocks, m = 3, 4, 8, 8, 16, 8, 9, 4

    def normal(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    pool = normal(n_blocks, BS, 128).at[..., dc + dr:].set(0.0)
    args = (
        normal(b, tq, h, dn), normal(b, tq, h, dr), pool,
        jnp.asarray(rng.integers(0, n_blocks, (b, m))),  # blocks aliased at will
        jnp.asarray(rng.integers(0, m * BS - tq, (b, 1))) + jnp.arange(tq),
        normal(dc, h * dn) / 4, normal(dc, h * dv) / 4,
    )
    kw = dict(block_size=BS, scale=0.3)
    np.testing.assert_allclose(
        paged_latent_attention(*args, absorbed=True, **kw),
        paged_latent_attention(*args, absorbed=False, **kw),
        rtol=2e-4, atol=2e-5,
    )


# -- the dispatch ----------------------------------------------------------


def _loop_over_experts(h, chosen, weight, gate, up, down, first):
    y = np.zeros((h.shape[0], down.shape[-1]), np.float64)
    for e in range(gate.shape[0]):
        w_e = np.where(np.asarray(chosen) == first + e, weight, 0.0).sum(-1)
        act = jax.nn.silu(h @ gate[e]) * (h @ up[e])
        y += w_e[:, None] * np.asarray(act @ down[e], np.float64)
    return y


def _expert_weights(rng, held=4, d=64, f=32):
    def normal(*shape):
        return jnp.asarray(rng.standard_normal(shape) / 8, jnp.float32)

    return normal(held, d, f), normal(held, d, f), normal(held, f, d)


def test_dropless_dispatch_when_one_expert_gets_every_token_and_one_none():
    rng = np.random.default_rng(3)
    t, k, first = 24, 4, 4
    h = jnp.asarray(rng.standard_normal((t, 64)), jnp.float32)
    gate, up, down = _expert_weights(rng)
    # every token chooses held expert 5; none chooses held expert 6; the
    # other choices fall on experts 4, 7 and on experts held elsewhere
    others = rng.choice([0, 1, 2, 3, 4, 7, 9, 12, 15], (t, k - 1))
    chosen = jnp.asarray(np.concatenate([np.full((t, 1), 5), others], 1))
    weight = jnp.asarray(rng.uniform(0.1, 1.0, (t, k)), jnp.float32)
    y, pairs = moe.held_experts_apply(
        h, chosen, weight, gate, up, down, first_expert=first
    )
    np.testing.assert_allclose(
        y, _loop_over_experts(h, chosen, weight, gate, up, down, first),
        rtol=1e-4, atol=1e-5,
    )
    assert int(pairs[1]) == t and int(pairs[2]) == 0
    held_pairs = np.sum((np.asarray(chosen) >= 4) & (np.asarray(chosen) < 8))
    assert int(jnp.sum(pairs)) == held_pairs  # nothing dropped, nothing extra


@pytest.mark.parametrize("masked", [False, True])
def test_dropless_dispatch_matches_a_loop_over_experts(toy, masked):
    rng = np.random.default_rng(4)
    t = 17
    h = jnp.asarray(rng.standard_normal((t, 64)), jnp.float32)
    gate, up, down = _expert_weights(rng)
    chosen, weight = moe.route_sigmoid_topk(
        h, toy.params[2]["router"], top_k=4, scale=2.5
    )
    mask = jnp.asarray(rng.random(t) < 0.5) if masked else None
    y, pairs = moe.held_experts_apply(
        h, chosen, weight, gate, up, down, first_expert=4, row_mask=mask
    )
    want = _loop_over_experts(h, chosen, weight, gate, up, down, 4)
    if masked:
        want = np.where(np.asarray(mask)[:, None], want, 0.0)
    np.testing.assert_allclose(y, want, rtol=1e-4, atol=1e-5)
    rows = np.asarray(mask) if masked else np.ones(t, bool)
    held = (np.asarray(chosen) >= 4) & (np.asarray(chosen) < 8) & rows[:, None]
    assert int(jnp.sum(pairs)) == held.sum()


def _whole_routed_layer(scored_with_bias: bool):
    """``(reference's feed_forward, its cfg, a routed block holding all 16
    experts, route)`` of a tower with a plain sigmoid router (this file's)
    or with a score bias an expert (the selecting tower's, whose reference
    is ``benchmarks/reference/dots3.py``)."""
    if not scored_with_bias:
        whole = Toy(first_expert=0, held=16, seed=5)
        block = whole.params[2]

        def route(h):
            return moe.route_sigmoid_topk(h, block["router"], top_k=4, scale=2.5)

        return whole.ref.feed_forward, whole.cfg, block, route
    from test_sparse_latent_lm import Toy as SparseToy

    whole = SparseToy(first_expert=0, held=16, seed=5)
    block = whole.params[2]
    assert float(jnp.abs(block["router_bias"]).max()) > 0

    def route(h):
        return moe.route_sigmoid_topk(
            h, block["router"], top_k=3, bias=block["router_bias"]
        )

    return whole.ref.feed_forward, whole.cfg, block, route


@pytest.mark.parametrize(
    "shares, scored_with_bias", [(4, False), (16, True)],
    ids=["4-shares", "16-shares-score-bias"],
)
def test_the_shares_of_a_layer_add_up_to_the_uncut_reference(
    shares, scored_with_bias
):
    """The guide's share test: each of the chips that share a 16-expert
    layer computes its own experts' part; with the shared expert counted
    once the parts add up to what the reference gives for the WHOLE
    layer.  With a score bias the choice follows the biased scores and the
    weights the unbiased ones, in every share alike."""
    feed_forward, cfg, block, route = _whole_routed_layer(scored_with_bias)
    rng = np.random.default_rng(5)
    h = jnp.asarray(rng.standard_normal((13, 64)), jnp.float32)

    def mm(a, b):
        return a @ b

    want = feed_forward(cfg, block, h, mm, 0)
    chosen, weight = route(h)
    total = paged_tower._gated(
        h, block["shared_gate"], block["shared_up"], block["shared_down"]
    )
    seen, each = 0, 16 // shares
    for chip in range(shares):
        held = slice(each * chip, each * (chip + 1))
        part, pairs = moe.held_experts_apply(
            h, chosen, weight, block["experts_gate"][held],
            block["experts_up"][held], block["experts_down"][held],
            first_expert=each * chip,
        )
        total = total + part
        seen += int(jnp.sum(pairs))
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-5)
    # every (token, choice) pair computed exactly once
    assert seen == 13 * chosen.shape[1]


def test_a_sliced_head_gives_the_matching_columns_of_the_whole_head(toy):
    rng = np.random.default_rng(6)
    x = jnp.asarray(rng.standard_normal((5, 64)), jnp.float32)
    whole = paged_tower._head_logits(toy.params, x, toy.model.rms_eps)
    for lo in (0, 64, 192):
        sliced = list(toy.params)
        sliced[-1] = dict(toy.params[-1], head=toy.params[-1]["head"][:, lo:lo + 64])
        np.testing.assert_allclose(
            paged_tower._head_logits(sliced, x, toy.model.rms_eps), whole[:, lo:lo + 64], rtol=1e-5, atol=1e-6
        )


# -- the tower through the latent pool -------------------------------------


def _prefill(toy, pools, table, prompt):
    """Chunked prefill of one prompt; logits at its last token."""
    padded = -(-len(prompt) // BS) * BS
    tokens = np.zeros(padded, np.int32)
    tokens[: len(prompt)] = prompt
    for c in range(padded // BS):
        last = (len(prompt) - 1) % BS if c == padded // BS - 1 else BS - 1
        pools, logits, _ = toy.model.prefill_chunk(
            toy.params, pools, jnp.asarray(table),
            jnp.asarray(tokens[None, c * BS:(c + 1) * BS]), jnp.int32(c * BS),
            block_size=BS, last=jnp.int32(last),
        )
    return pools, logits


def test_prefill_chunks_then_decode_steps_match_the_reference_forward(toy):
    rng = np.random.default_rng(7)
    seq = _tokens(rng, 29)
    n_prompt = 21
    want = toy.reference_logits(seq)
    pools = toy.model.init_pools(toy.params, 12, BS)
    table = np.array([3, 7, 1, 9, 5, 0, 0, 0], np.int32)
    pools, logits = _prefill(toy, pools, table, seq[:n_prompt])
    np.testing.assert_allclose(logits[0], want[n_prompt - 1], rtol=2e-4, atol=2e-4)
    # a second, idle row rides along: it writes to the null block and is
    # routed nowhere
    tables = jnp.asarray(np.stack([table, np.zeros_like(table)]))
    for pos in range(n_prompt, len(seq)):
        pools, logits, load = toy.model.decode_step(
            toy.params, pools, tables, jnp.asarray([seq[pos], 0]),
            jnp.asarray([pos, 0]), block_size=BS,
            write_mask=jnp.asarray([True, False]),
        )
        np.testing.assert_allclose(logits[0], want[pos], rtol=2e-4, atol=2e-4)
        assert int(jnp.sum(load["pairs"])) <= 3 * 4  # one live row, 3 routed layers


def test_a_prefix_hit_and_a_copy_on_write_split_give_a_cold_prefill_logits(toy):
    rng = np.random.default_rng(8)
    shared, tail_a, tail_b = _tokens(rng, 2 * BS), _tokens(rng, 5), _tokens(rng, 11)
    prompt_b = np.concatenate([shared, tail_b])
    cold = toy.model.init_pools(toy.params, 12, BS)
    _, want = _prefill(toy, cold, np.array([1, 2, 3, 4, 0, 0, 0, 0], np.int32), prompt_b)

    pools = toy.model.init_pools(toy.params, 12, BS)
    table_a = np.array([5, 6, 7, 0, 0, 0, 0, 0], np.int32)
    pools, _ = _prefill(toy, pools, table_a, np.concatenate([shared, tail_a]))
    # a prefix-cache hit: B's table maps A's two shared blocks and only
    # B's own tail is prefilled, from the third chunk on
    table_b = np.array([5, 6, 8, 9, 0, 0, 0, 0], np.int32)
    tokens = np.zeros(4 * BS, np.int32)
    tokens[: len(prompt_b)] = prompt_b
    for c in (2, 3):
        last = (len(prompt_b) - 1) % BS if c == 3 else BS - 1
        pools, got, _ = toy.model.prefill_chunk(
            toy.params, pools, jnp.asarray(table_b),
            jnp.asarray(tokens[None, c * BS:(c + 1) * BS]), jnp.int32(c * BS),
            block_size=BS, last=jnp.int32(last),
        )
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    # a copy-on-write split of a shared block: the copy reads the same
    pools = copy_paged_block(pools, jnp.int32(6), jnp.int32(10))
    table_split = jnp.asarray(np.array([5, 10, 8, 9, 0, 0, 0, 0], np.int32))
    step = lambda table: toy.model.decode_step(  # noqa: E731
        toy.params, pools, table[None], jnp.asarray([17]),
        jnp.asarray([len(prompt_b)]), block_size=BS,
    )[1]
    np.testing.assert_allclose(
        step(table_split), step(jnp.asarray(table_b)), rtol=1e-6, atol=1e-6
    )


# -- through the engine ----------------------------------------------------


def test_the_engine_serves_the_reference_greedy_through_the_latent_pool(toy):
    rng = np.random.default_rng(9)
    eng = toy.engine()
    assert eng._pools[0]["kv"].shape == (eng.n_blocks, BS, 128)
    assert eng.block_bytes == 4 * BS * 128 * 4  # 4 layers of float32 rows
    ids = [eng.submit(_tokens(rng, n), new) for n, new in ((21, 12), (9, 5), (16, 7))]
    eng.run()
    for rid in ids:
        completion = eng.completions[rid]
        assert completion.finish_reason in ("budget", "eos")
        assert toy.served_gaps(completion).max() < 1e-4


def test_a_prefix_cache_hit_serves_what_a_cold_engine_serves(toy):
    rng = np.random.default_rng(10)
    shared = _tokens(rng, 2 * BS)
    prompts = [np.concatenate([shared, _tokens(rng, n)]) for n in (3, 11)]
    cold = [toy.engine(prefix_cache=False) for _ in prompts]
    want = []
    for eng, p in zip(cold, prompts):
        rid = eng.submit(p, 6)
        eng.run()
        want.append(eng.completions[rid].tokens)
    eng = toy.engine(batch_size=1)
    hits0 = _counter("znicz_serve_prefix_hits_total")
    for p, tokens in zip(prompts, want):
        rid = eng.submit(p, 6)
        eng.run()
        np.testing.assert_array_equal(eng.completions[rid].tokens, tokens)
    assert _counter("znicz_serve_prefix_hits_total") - hits0 == 2


def test_a_fully_cached_prompt_splits_its_last_block_copy_on_write(toy):
    rng = np.random.default_rng(11)
    p = _tokens(rng, 2 * BS)  # exactly two blocks
    eng = toy.engine()
    first = eng.submit(p, 6)
    eng.run()
    again = eng.submit(p, 6)
    eng.run()
    np.testing.assert_array_equal(
        eng.completions[again].tokens, eng.completions[first].tokens
    )
    assert eng.stats()["prefix_cache"]["cow_splits"] >= 1
    assert toy.served_gaps(eng.completions[again]).max() < 1e-4


def test_the_decode_write_guard_copies_a_shared_latent_block(toy):
    rng = np.random.default_rng(12)
    eng = toy.engine(batch_size=1)
    eng.submit(_tokens(rng, 5), 8)
    eng._admit_pending()
    eng._prefill_tick()
    blk = int(eng._kinds[0].row_blocks[0][0])
    eng._kinds[0].cache[b"eager-fill"] = blk
    eng._kinds[0].block_hash[blk] = b"eager-fill"
    eng.run()
    completion = next(iter(eng.completions.values()))
    assert toy.served_gaps(completion).max() < 1e-4
    stats = eng.stats()
    assert stats["prefix_cache"]["cow_splits"] >= 1
    assert ("cow", BS) in stats["programs"]


def test_expert_load_comes_back_with_the_chunks_and_skips_idle_rows(toy):
    names = {
        n: "znicz_serve_moe_" + n + "_total"
        for n in ("pairs", "busiest_pairs", "idle_experts", "layer_steps")
    }
    before = {
        (n, ph): _counter(name, phase=ph)
        for n, name in names.items() for ph in ("decode", "prefill")
    }
    steps0 = _counter("znicz_serve_decode_steps_total")
    chunks0 = _counter("znicz_serve_prefill_chunks_total")
    eng = toy.engine()
    eng.submit(_tokens(np.random.default_rng(13), 19), 9)
    eng.run()
    d = {k: _counter(names[k[0]], phase=k[1]) - v for k, v in before.items()}
    steps = _counter("znicz_serve_decode_steps_total") - steps0
    chunks = _counter("znicz_serve_prefill_chunks_total") - chunks0
    assert d["layer_steps", "decode"] == 3 * steps
    assert d["layer_steps", "prefill"] == 3 * chunks == 9
    # one live row of four slots: the three idle rows are routed nowhere
    assert 0 < d["pairs", "decode"] <= steps * 3 * 4
    assert d["busiest_pairs", "decode"] <= d["pairs", "decode"]
    # an expert is idle or it computed at least one pair
    hit = 4 * d["layer_steps", "decode"] - d["idle_experts", "decode"]
    assert 0 < hit <= d["pairs", "decode"]
    # the prompt's 19 tokens (right-padding excluded) choose 4 of 16 each
    assert 0 < d["pairs", "prefill"] <= 19 * 3 * 4


@pytest.mark.parametrize("tower", ["latent_in_place", "latent_gathered", "kv"])
def test_the_gathered_counter_counts_what_the_attention_form_read(
    tower, monkeypatch
):
    """``znicz_serve_decode_gathered_tokens_total`` over a served stream:
    the decoding rows' lengths in whole blocks, step by step, where the
    latent pool is read in place; every slot's window where a window is
    gathered (the latent tower off the TPU, the K/V tower anywhere)."""
    from znicz_tpu.ops import attention

    if tower == "kv":
        from znicz_tpu.workflow.transformer import init_lm_params

        eng = PagedDecodeEngine(
            init_lm_params(256, 32, 2, 4, max_seq=64), n_heads=4, eos_id=0,
            block_size=BS, batch_size=4, max_seq=64, admit_every=4,
        )
    else:
        if tower == "latent_in_place":
            # this process computes on the CPU: the op is told that a
            # decode step reads in place (the kernel runs interpreted)
            monkeypatch.setattr(
                attention, "_reads_pool_in_place", lambda tq: tq == 1
            )
        # a model of its own, so that the decode chunk is traced here and
        # not found among the programs other tests compiled
        eng = Toy(max_positions=250 + (tower == "latent_in_place")).engine()
    chunks = []
    count = eng._count_gathered
    monkeypatch.setattr(
        eng, "_count_gathered",
        lambda steps, window, *read: (
            chunks.append((steps, window)), count(steps, window, *read)
        ),
    )
    rng = np.random.default_rng(14)
    before = _counter("znicz_serve_decode_gathered_tokens_total")
    ids = [eng.submit(_tokens(rng, n), new) for n, new in ((21, 9), (5, 12), (16, 3))]
    eng.run()
    gathered = _counter("znicz_serve_decode_gathered_tokens_total") - before
    assert sum(steps for steps, _ in chunks) > 0
    if tower == "latent_in_place":
        # a request of n prompt tokens decodes its k-th token at position
        # n + k, attending n + k + 1 keys; its first token came from the
        # prefill and its last is fed to no step
        done = [eng.completions[rid] for rid in ids]
        assert gathered == sum(
            -(-(len(c.tokens) - c.n_new + k + 1) // BS) * BS
            for c in done for k in range(c.n_new - 1)
        )
    else:
        assert gathered == sum(
            steps * eng.batch_size * window * BS for steps, window in chunks
        )


def test_what_the_tower_is_not_served_with_is_refused_by_name(toy):
    with pytest.raises(SpeculationUnsupportedError, match="LatentMoEModel"):
        toy.engine(spec_k=2)
    with pytest.raises(ValueError, match="topk_method"):
        latent_lm.LatentMoEModel.from_config(
            dict(toy.cfg, topk_method="noaux_tc"), first_expert=0, max_positions=64
        )
    with pytest.raises(ValueError, match="exceeds the positional"):
        toy.engine(max_seq=512)
