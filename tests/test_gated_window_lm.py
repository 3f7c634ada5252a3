"""The head-gated window tower (workflow/gated_window_lm.py: query heads a
layer kind, a rotary a kind, a gate a head, a leading dense layer, sigmoid-
routed experts beside a shared one) against the equations of
``benchmarks/reference/laguna.py`` at a small size: 4 layers (full + dense,
sliding, sliding, full), hidden 64, 12 query heads on the full layers and 16
on the sliding ones over 2 K/V heads of 16 (groups of 6 and of 8), 8 experts
of 32 with 2 a token beside a shared one, vocabulary 256, float32 weights,
seeded.  The window is 16 tokens at a block of 4 and YaRN stretches an
original context of 16 positions by 8, so a row of a few dozen tokens
crosses the window, wraps its ring table and runs past ``original_max``.

ONE fixture (``toy``) builds the model, its parameters and the reference's
view of the same arrays.

The model-configs guide's share-adds-up test does not apply: no share of a
layer is cut (every expert, every head and the whole vocabulary are held).

Tolerances: as in tests/test_window_gqa_lm.py (the same float32 sums in
another order move a logit of size ~1 by ~1e-5; 2e-4 holds that with room,
and every mistake the tests look for moves logits by 1e-2 and more)."""

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_window_gqa_lm import _counter
from znicz_tpu.ops import rope
from znicz_tpu.ops.attention import gqa_cache_row, paged_gqa_attention
from znicz_tpu.services.engine import PagedDecodeEngine
from znicz_tpu.workflow import gated_window_lm

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BS = 4  # block size
WINDOW = 16
TOL = dict(rtol=2e-4, atol=2e-4)
SIZES = dict(d_model=64, vocab=256, d_ff_dense=96, d_ff_expert=32, n_experts=8)
FULL, SLIDING = "full_attention", "sliding_attention"
CFG = {
    "name": "toy-laguna", "model_type": "laguna", "num_hidden_layers": 4,
    "hidden_size": 64, "intermediate_size": 96, "num_attention_heads": 12,
    "num_key_value_heads": 2, "head_dim": 16, "attention_bias": False,
    "rms_norm_eps": 1e-6, "num_experts": 8, "num_experts_per_tok": 2,
    "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32,
    "tie_word_embeddings": False, "gating": True, "sliding_window": WINDOW,
    "moe_routed_scaling_factor": 2.5, "moe_apply_router_weight_on_input": False,
    "rope_parameters": {
        FULL: {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 8,
            "original_max_position_embeddings": 16, "beta_slow": 1,
            "beta_fast": 4, "attention_factor": 1.2079,
            "partial_rotary_factor": 0.5,
        },
        SLIDING: {
            "rope_type": "default", "rope_theta": 10000,
            "partial_rotary_factor": 1,
        },
    },
    # the published period, longer than the layers run: the first 4 count
    "layer_types": [FULL, SLIDING, SLIDING, FULL, FULL, SLIDING],
    "mlp_layer_types": ["dense"] + ["sparse"] * 5,
    "num_attention_heads_per_layer": [12, 16, 16, 12, 12, 16],
}


def _load_reference():
    spec = importlib.util.spec_from_file_location(
        "laguna_reference_for_tests",
        os.path.join(REPO, "benchmarks", "reference", "laguna.py"),
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    module.BLOCK = 4  # its row blocks, at toy length
    return module


class Toy:
    def __init__(self, seed=1, max_positions=128):
        self.ref = _load_reference()
        self.cfg = CFG
        self.model = gated_window_lm.GatedWindowGQAMoEModel.from_config(
            CFG, max_positions=max_positions
        )
        self.params = gated_window_lm.init_params(self.model, seed=seed, **SIZES)
        # a choice bias that changes the choice: the weights stay the
        # unbiased scores' (zeros in a seeded run of the cell)
        bias = np.random.default_rng(seed).standard_normal(8) * 0.3
        for block in self.params[2:-1]:
            block["router_bias"] = jnp.asarray(bias, jnp.float32)
        self.w = {
            "embed": self.params[0]["embed"], "blocks": self.params[1:-1],
            "final_norm": self.params[-1]["final_norm"],
            "head": self.params[-1]["head"],
        }

    def reference_logits(self, tokens, **kw):
        return np.asarray(self.ref.logits(self.cfg, self.w, list(tokens), **kw))

    def engine(self, **kw):
        kw.setdefault("batch_size", 2)
        kw.setdefault("max_seq", 64)
        kw.setdefault("admit_every", 4)
        return PagedDecodeEngine(
            self.params, n_heads=12, eos_id=0, block_size=BS,
            model=self.model, **kw
        )

    def served_gaps(self, completion):
        seq = list(completion.tokens)
        n_prompt = len(seq) - completion.n_new
        logits = self.ref.logits(
            self.cfg, self.w, seq[:-1], first_row=n_prompt - 1
        )
        return np.asarray(self.ref.served_gaps(logits, seq[n_prompt:]))

    def through_the_cache(self, tokens, n_prompt):
        """Prefill chunks over ``tokens[:n_prompt]`` then decode steps over
        the rest, through pools and tables as the engine lays them out;
        logits [len(tokens) - n_prompt + 1, vocab] from the prompt's last
        position on."""
        model, params = self.model, self.params
        tokens = np.asarray(tokens)
        total = len(tokens)
        width = {"global": -(-total // BS), "window": WINDOW // BS + 2}
        pools = model.init_pools(
            params, {"global": width["global"] + 1, "window": width["window"] + 1}, BS
        )
        # block j of a kind lives in pool block 1 + j % width
        table = {k: 1 + jnp.arange(w, dtype=jnp.int32) for k, w in width.items()}
        out = []
        padded = np.zeros(-(-n_prompt // BS) * BS, np.int32)
        padded[:n_prompt] = tokens[:n_prompt]
        for c in range(len(padded) // BS):
            last = (n_prompt - 1) % BS if (c + 1) * BS >= n_prompt else None
            pools, logits, _ = model.prefill_chunk(
                params, pools, table, jnp.asarray(padded[None, c * BS:(c + 1) * BS]),
                jnp.int32(c * BS), block_size=BS,
                last=None if last is None else jnp.int32(last),
            )
        out.append(logits[0])
        for pos in range(n_prompt, total):
            pools, logits, load = model.decode_step(
                params, pools, {k: t[None] for k, t in table.items()},
                jnp.asarray(tokens[pos:pos + 1]), jnp.asarray([pos]),
                block_size=BS,
            )
            out.append(logits[0])
        return np.stack(out), load


@pytest.fixture(scope="module")
def toy():
    return Toy()


def _tokens(rng, n):
    return rng.integers(1, SIZES["vocab"], n)


# -- the small pieces ------------------------------------------------------


def test_the_rotary_frequencies_match_the_reference(toy):
    full = CFG["rope_parameters"][FULL]
    np.testing.assert_allclose(
        rope.yarn_inv_freq(
            8, 5e5, factor=8, original_max=16, beta_fast=4, beta_slow=1
        ),
        toy.ref.yarn_freqs(8, full), rtol=1e-6,
    )
    # the stretch is there: the slowest pair turns 8 times slower than plain
    assert float(toy.ref.yarn_freqs(8, full)[-1]) == pytest.approx(
        float(rope.plain_inv_freq(8, 5e5)[-1]) / 8, rel=1e-5
    )
    np.testing.assert_allclose(
        rope.plain_inv_freq(16, 1e4), toy.ref.plain_freqs(16, 1e4), rtol=1e-6
    )


def test_a_global_layer_turns_half_a_head_and_scales_what_it_turns(toy):
    a = np.random.default_rng(0).standard_normal((1, 3, 2, 16)).astype(np.float32)
    pos = jnp.asarray([[5, 17, 40]])
    got = np.asarray(toy.model._turn(jnp.asarray(a), pos, False))
    np.testing.assert_array_equal(got[..., 8:], a[..., 8:])
    want = toy.ref.rot(
        jnp.asarray(a[0]), pos[0], toy.ref.yarn_freqs(8, CFG["rope_parameters"][FULL]),
        1.2079,
    )
    np.testing.assert_allclose(got[0], want, **TOL)
    # a window layer: the whole head, unscaled
    want = toy.ref.rot(jnp.asarray(a[0]), pos[0], toy.ref.plain_freqs(16, 1e4))
    np.testing.assert_allclose(
        np.asarray(toy.model._turn(jnp.asarray(a), pos, True))[0], want, **TOL
    )


@pytest.mark.parametrize("heads", [12, 16], ids=["groups_of_6", "groups_of_8"])
@pytest.mark.parametrize("window", [None, WINDOW], ids=["global", "window"])
def test_the_grouped_prefill_form_is_the_gathered_form(heads, window):
    """A chunk of 4 queries over a table that holds 9 blocks (a ring of 6
    for the window), past a window's worth of keys: the walk under a
    running softmax, products grouped a K/V head, against the as-stored
    layout's one product over the gathered table."""
    rng = np.random.default_rng(heads)
    n_blocks, width = 12, 9 if window is None else 6
    pool = jnp.asarray(rng.standard_normal((n_blocks, BS, 2 * 2 * 16)), jnp.float32)
    table = jnp.asarray(rng.permutation(np.arange(1, n_blocks))[None, :width])
    q = jnp.asarray(rng.standard_normal((1, BS, heads, 16)), jnp.float32)
    q_pos = jnp.asarray([[32, 33, 34, 35]])
    kw = dict(block_size=BS, n_kv_heads=2, window=window)
    np.testing.assert_allclose(
        paged_gqa_attention(q, pool, table, q_pos, grouped_prefill=True, **kw),
        paged_gqa_attention(q, pool, table, q_pos, **kw), **TOL,
    )


# -- the tower against the reference ------------------------------------------


@pytest.mark.parametrize(
    "n_prompt,total", [(7, 13), (22, 41)],
    ids=["shorter_than_the_window", "past_the_window_and_original_max"],
)
def test_prefill_then_decode_through_the_cache_is_the_full_forward_pass(
    toy, n_prompt, total
):
    tokens = _tokens(np.random.default_rng(total), total)
    got, load = toy.through_the_cache(tokens, n_prompt)
    want = toy.reference_logits(tokens, first_row=n_prompt - 1)
    np.testing.assert_allclose(got, want, **TOL)
    # three routed layers, 2 pairs a token each; the dense layer counts none
    assert int(np.sum(load["pairs"])) == 3 * 2
    assert toy.model.routed_layers(toy.params) == 3


def test_the_reference_after_a_prefix_state_is_the_reference_in_one_pass(toy):
    tokens = _tokens(np.random.default_rng(5), 52)
    whole = toy.reference_logits(tokens, first_row=40)
    state = toy.ref.prefix_state(CFG, toy.w, list(tokens[:24]))
    # a sliding layer keeps the last window - 1 keys of the prefix alone
    assert state[1][0].shape[0] == WINDOW - 1 and state[0][0].shape[0] == 24
    tail = toy.reference_logits(
        tokens[24:], state=state, n_past=24, first_row=16, pad_to=40
    )
    np.testing.assert_allclose(tail, whole, **TOL)


CONTROLS = {
    "float8_products": lambda ref: {
        "cast": lambda a: a.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    },
    "float8_cache": lambda ref: {
        "cache_cast": lambda a: a.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    },
    "no_gate": lambda ref: {"gate": False},
    "window_doubled": lambda ref: {"window": 2 * WINDOW},
    "plain_rotary_on_full_layers": lambda ref: {"full_rope": "plain"},
}


@pytest.mark.parametrize("control", sorted(CONTROLS))
def test_each_control_moves_the_reference_logits(toy, control):
    tokens = _tokens(np.random.default_rng(9), 44)
    sound = toy.reference_logits(tokens, first_row=40)
    low = toy.reference_logits(tokens, first_row=40, **CONTROLS[control](toy.ref))
    assert np.abs(low - sound).max() > 1e-2


# -- behind the engine --------------------------------------------------------


def test_the_engine_serves_the_reference_greedy_through_both_kinds(toy):
    rng = np.random.default_rng(11)
    eng = toy.engine()
    ids = [
        eng.submit(_tokens(rng, n), max_new_tokens=m)
        for n, m in ((5, 6), (23, 20), (37, 9))
    ]
    before = _counter("znicz_serve_decode_cached_rows_total", kind="window")
    eng.run()
    for rid in ids:
        assert toy.served_gaps(eng.completions[rid]).max() < 1e-4
    assert _counter("znicz_serve_decode_cached_rows_total", kind="window") > before
    assert eng.stats()["kinds"]["window"]["blocks_released_behind_window"] > 0
    assert not eng.prefix_cache  # a window kind: off unless asked for by name


@pytest.fixture
def shared_runs(monkeypatch):
    """The TPU's decode path on the CPU (the kernels interpreted), with
    chunks of 2 blocks and tiles of 2 rows; returns the tile's rows."""
    from znicz_tpu.ops import attention as att
    from znicz_tpu.ops.pallas import latent_attention as la

    monkeypatch.setattr(att, "_reads_pool_in_place", lambda tq: tq == 1)
    monkeypatch.setattr(la, "CHUNK_BLOCKS", 2)
    monkeypatch.setattr(la, "TILE_ROWS", 2)
    return 2


def _rows_by_kind(name):
    return {
        kind: _counter(f"znicz_serve_decode_{name}_rows_total", kind=kind)
        for kind in ("global", "window")
    }


def test_a_step_counts_a_shared_run_once_and_what_its_queries_met(toy, shared_runs):
    """Three rows decode: two open with the same 8 global blocks, the third
    with its own.  A global layer fetches the run once, the members' own
    blocks and the third row whole; its queries met every row's length."""
    model, params = toy.model, toy.params
    pools = model.init_pools(params, {"global": 64, "window": 32}, BS)
    pos = np.asarray([37, 45, 41, 0])
    table = np.zeros((4, 16), np.int32)
    table[0, :10] = list(range(1, 9)) + [20, 21]
    table[1, :12] = list(range(1, 9)) + [22, 23, 24, 25]
    table[2, :11] = range(30, 41)
    ring = 1 + np.arange(4 * 6, dtype=np.int32).reshape(4, 6)
    _, _, load = model.decode_step(
        params, pools, {"global": jnp.asarray(table), "window": jnp.asarray(ring)},
        jnp.asarray([3, 4, 5, 0]), jnp.asarray(pos), block_size=BS,
        write_mask=jnp.asarray([True, True, True, False]),
    )
    in_blocks = [-(-(p + 1) // BS) * BS for p in pos[:3]]
    assert int(load["attended_rows_by_kind"]["global"]) == sum(in_blocks)
    run = 8 * BS  # four chunks of two blocks, all under the shorter length
    assert int(load["cached_rows_by_kind"]["global"]) == (
        run + (in_blocks[0] - run) + (in_blocks[1] - run) + in_blocks[2]
    )
    # a window layer's ring differs a row: read as it is attended
    window = int(load["cached_rows_by_kind"]["window"])
    assert window == int(load["attended_rows_by_kind"]["window"]) > 0
    layers = model.layer_kinds
    assert int(load["cached_rows"]) == sum(
        int(load["cached_rows_by_kind"][k]) for k in layers
    ) // len(layers)


def test_a_served_prefix_is_read_once_a_tile_by_one_decode_program(shared_runs):
    """1, R + 1 and every slot live behind a primed prefix: the answers are
    the reference's, the global kind fetches less than its queries meet as
    soon as two rows share, and the live rows never name a new program."""
    toy = Toy(max_positions=131)  # a model of its own: its programs are traced here
    rng = np.random.default_rng(47)
    prefix = _tokens(rng, 32)  # 8 blocks: every decode chunk at the widest rung
    eng = toy.engine(prefix_cache=True, batch_size=4, max_seq=64)
    eng.submit(prefix, max_new_tokens=1)
    eng.run()
    for live in (1, shared_runs + 1, 4):
        fetched, attended = _rows_by_kind("cached"), _rows_by_kind("attended")
        ids = [
            eng.submit(np.concatenate([prefix, _tokens(rng, 3 + i)]), max_new_tokens=7)
            for i in range(live)
        ]
        eng.run()
        for rid in ids:
            done = eng.completions[rid]
            assert done.timings["cached_tokens"] == 32
            assert toy.served_gaps(done).max() < 1e-4
        fetched = {k: v - fetched[k] for k, v in _rows_by_kind("cached").items()}
        attended = {k: v - attended[k] for k, v in _rows_by_kind("attended").items()}
        assert fetched["window"] == attended["window"] > 0
        if live == 1:
            assert fetched["global"] == attended["global"] > 0
        else:
            assert 0 < fetched["global"] < attended["global"]
    decode_programs = [k for k in eng._programs if k[0] == "paged_chunk"]
    assert len(decode_programs) == 1, decode_programs


def test_from_config_reads_the_published_keys_and_refuses_what_is_not_built():
    path = os.path.join(REPO, "benchmarks", "configs", "laguna-xs2-stage1.json")
    with open(path) as f:
        cfg = json.load(f)
    model = gated_window_lm.GatedWindowGQAMoEModel.from_config(
        cfg, max_positions=cfg["serving"]["max_seq"]
    )
    assert (model.n_heads, model.global_heads, model.n_kv_heads) == (64, 48, 8)
    assert model.windowed == (False, True, True, True, False, True, True)
    assert (model.window, model.top_k, model.global_rotary_dim) == (512, 8, 64)
    assert model.attention_factor == pytest.approx(1.41589, rel=1e-5)
    assert [k.window for k in model.cache_kinds] == [None, 512]
    hash(model)  # a static argument of the engine's programs

    def refused(match, **changes):
        with pytest.raises(ValueError, match=match):
            gated_window_lm.GatedWindowGQAMoEModel.from_config(
                {**CFG, **changes}, max_positions=64
            )

    refused("layer_types", layer_types=[FULL, "linear_attention", SLIDING, FULL])
    refused("one head count", num_attention_heads_per_layer=[12, 16, 8, 12])
    refused("gating", gating=False)
    refused("attention_bias", attention_bias=True)
    refused("tie_word_embeddings", tie_word_embeddings=True)
    refused("moe_apply_router_weight_on_input", moe_apply_router_weight_on_input=True)
    refused("mlp_layer_types", mlp_layer_types=["sparse"] * 4)
    plain = {**CFG["rope_parameters"], FULL: CFG["rope_parameters"][SLIDING]}
    refused("rope_parameters", rope_parameters=plain)
