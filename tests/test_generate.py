"""KV-cache autoregressive decode vs the full forward (golden parity).

The decode path (znicz_tpu/workflow/generate.py) must reproduce
``lm_apply``'s logits position-by-position — prefill and incremental steps
both — and ``generate`` must emit exactly the tokens a full re-forward
would choose (greedy) while never re-running earlier positions.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from znicz_tpu.core import prng
from znicz_tpu.services.engine import PagedDecodeEngine
from znicz_tpu.workflow import generate as G
from znicz_tpu.workflow.transformer import init_lm_params, lm_apply


def _setup(moe_experts=0, seed=27, t_max=24):
    prng.seed_all(seed)
    vocab, d, heads = 17, 32, 4
    params = init_lm_params(
        vocab, d, 2, heads, max_seq=t_max, moe_experts=moe_experts
    )
    tokens = np.random.default_rng(7).integers(
        0, vocab, (3, 12)
    ).astype(np.int32)
    return params, tokens, heads, vocab


class TestDecodeGolden:
    def test_teacher_forced_logits_match_full_forward(self):
        params, tokens, heads, _ = _setup()
        full = np.asarray(lm_apply(params, jnp.asarray(tokens), n_heads=heads))
        caches = G.init_kv_cache(params, 3, 12, n_heads=heads)
        caches, lg = G.prefill(
            params, jnp.asarray(tokens[:, :4]), caches, n_heads=heads
        )
        np.testing.assert_allclose(
            np.asarray(lg), full[:, 3], rtol=1e-4, atol=1e-5
        )
        for p in range(4, 12):
            caches, lg = G.decode_step(
                params, caches, jnp.asarray(tokens[:, p]), p, n_heads=heads
            )
            np.testing.assert_allclose(
                np.asarray(lg), full[:, p], rtol=1e-4, atol=1e-5
            )

    def test_moe_decode_matches_full_forward(self):
        # the MoE FFN rides the same _block_ffn in both paths
        params, tokens, heads, _ = _setup(moe_experts=4, seed=31)
        full = np.asarray(
            lm_apply(params, jnp.asarray(tokens), n_heads=heads, moe_top_k=2)
        )
        caches = G.init_kv_cache(params, 3, 12, n_heads=heads)
        caches, lg = G.prefill(
            params, jnp.asarray(tokens[:, :6]), caches,
            n_heads=heads, moe_top_k=2,
        )
        np.testing.assert_allclose(
            np.asarray(lg), full[:, 5], rtol=1e-4, atol=1e-5
        )
        for p in range(6, 12):
            caches, lg = G.decode_step(
                params, caches, jnp.asarray(tokens[:, p]), p,
                n_heads=heads, moe_top_k=2,
            )
            np.testing.assert_allclose(
                np.asarray(lg), full[:, p], rtol=1e-4, atol=1e-5
            )

    def test_greedy_generate_matches_full_reforward(self):
        # every emitted token == the argmax a full forward over the
        # (prompt + generated-so-far) prefix would choose
        params, tokens, heads, _ = _setup()
        out = np.asarray(
            G.generate(
                params, jnp.asarray(tokens[:, :4]),
                n_heads=heads, max_new_tokens=8,
            )
        )
        assert out.shape == (3, 12)
        assert (out[:, :4] == tokens[:, :4]).all()
        full = np.asarray(lm_apply(params, jnp.asarray(out), n_heads=heads))
        for p in range(4, 12):
            np.testing.assert_array_equal(
                out[:, p], np.argmax(full[:, p - 1], axis=-1)
            )

    def test_temperature_sampling_reproducible_and_in_vocab(self):
        params, tokens, heads, vocab = _setup()
        kw = dict(n_heads=heads, max_new_tokens=6, temperature=0.8)
        a = np.asarray(
            G.generate(
                params, jnp.asarray(tokens[:, :4]),
                rng=jax.random.key(5), **kw,
            )
        )
        b = np.asarray(
            G.generate(
                params, jnp.asarray(tokens[:, :4]),
                rng=jax.random.key(5), **kw,
            )
        )
        np.testing.assert_array_equal(a, b)  # same key -> same draw
        assert (a[:, 4:] >= 0).all() and (a[:, 4:] < vocab).all()
        c = np.asarray(
            G.generate(
                params, jnp.asarray(tokens[:, :4]),
                rng=jax.random.key(6), **kw,
            )
        )
        assert not (a == c).all()  # different key -> different draw

    def test_capacity_exceeded_raises(self):
        params, tokens, heads, _ = _setup(t_max=10)
        with pytest.raises(ValueError, match="positional table"):
            G.generate(
                params, jnp.asarray(tokens[:, :4]),
                n_heads=heads, max_new_tokens=8,
            )

    def test_workflow_generate_method(self):
        # the user-facing path: train a workflow, call wf.generate()
        from znicz_tpu.loader.fullbatch import FullBatchLoader
        from znicz_tpu.workflow.transformer import TransformerLMWorkflow

        tokens = np.random.default_rng(3).integers(
            0, 16, (16, 24)
        ).astype(np.int32)
        prng.seed_all(77)
        ld = FullBatchLoader({"train": tokens.copy()}, minibatch_size=8)
        wf = TransformerLMWorkflow(
            ld, vocab=16, d_model=16, n_layers=1, n_heads=2, max_epochs=1,
        )
        wf.initialize(seed=77)
        wf.run()
        out = np.asarray(
            wf.generate(tokens[:2, :6], max_new_tokens=8)
        )
        assert out.shape == (2, 14)
        # tokens equal what the module-level greedy path produces
        ref = np.asarray(
            G.generate(
                wf.state.params, jnp.asarray(tokens[:2, :6]),
                n_heads=2, max_new_tokens=8,
            )
        )
        np.testing.assert_array_equal(out, ref)

    def test_workflow_generate_rejects_pipelined(self):
        from znicz_tpu.loader.fullbatch import FullBatchLoader
        from znicz_tpu.parallel import DataParallel, make_mesh
        from znicz_tpu.workflow.transformer import TransformerLMWorkflow

        tokens = np.zeros((32, 16), np.int32)
        ld = FullBatchLoader({"train": tokens}, minibatch_size=16)
        wf = TransformerLMWorkflow(
            ld, vocab=4, d_model=8, n_layers=2, n_heads=2, max_epochs=1,
            pipeline_parallel=True, parallel=DataParallel(make_mesh(4, 1, 2)),
        )
        wf.initialize(seed=5)
        with pytest.raises(ValueError, match="pipelined"):
            wf.generate(tokens[:2, :4], max_new_tokens=2)

    def test_tp_sharded_params_decode_matches_replicated(self):
        # decode at scale: generate() is one jitted scan, so GSPMD
        # partitions it for lm_tp_rules-sharded params (head/QKV column,
        # wo/w_down row) with the same tokens as the replicated run
        import jax.tree_util as jtu
        from jax.sharding import NamedSharding

        from znicz_tpu.parallel import make_mesh
        from znicz_tpu.workflow.transformer import lm_tp_rules

        params, tokens, heads, _ = _setup()
        # vocab 17 does not divide the 4-way model axis; re-init at 16
        prng.seed_all(27)
        from znicz_tpu.workflow.transformer import init_lm_params

        params = init_lm_params(16, 32, 2, heads, max_seq=24)
        prompt = jnp.asarray(tokens[:, :6] % 16)
        ref = np.asarray(
            G.generate(params, prompt, n_heads=heads, max_new_tokens=10)
        )
        mesh = make_mesh(2, 4)

        def place(path, leaf):
            spec = lm_tp_rules(jtu.keystr(path), leaf)
            return jax.device_put(leaf, NamedSharding(mesh, spec))

        sharded = jtu.tree_map_with_path(place, params)
        assert not sharded[1]["wq"].is_fully_replicated
        out = np.asarray(
            G.generate(sharded, prompt, n_heads=heads, max_new_tokens=10)
        )
        np.testing.assert_array_equal(ref, out)

    def test_temperature_without_rng_raises(self):
        params, tokens, heads, _ = _setup()
        with pytest.raises(ValueError, match="rng"):
            G.generate(
                params, jnp.asarray(tokens[:, :4]),
                n_heads=heads, max_new_tokens=2, temperature=0.7,
            )


class TestSamplingTruncation:
    def test_top_k_1_equals_greedy(self):
        params, tokens, heads, _ = _setup()
        greedy = np.asarray(
            G.generate(
                params, jnp.asarray(tokens[:, :4]),
                n_heads=heads, max_new_tokens=6,
            )
        )
        k1 = np.asarray(
            G.generate(
                params, jnp.asarray(tokens[:, :4]),
                n_heads=heads, max_new_tokens=6,
                temperature=1.0, top_k=1, rng=jax.random.key(2),
            )
        )
        np.testing.assert_array_equal(greedy, k1)

    def test_tiny_top_p_equals_greedy(self):
        # top_p -> 0 keeps only the argmax token (always retained)
        params, tokens, heads, _ = _setup()
        greedy = np.asarray(
            G.generate(
                params, jnp.asarray(tokens[:, :4]),
                n_heads=heads, max_new_tokens=6,
            )
        )
        p0 = np.asarray(
            G.generate(
                params, jnp.asarray(tokens[:, :4]),
                n_heads=heads, max_new_tokens=6,
                temperature=1.0, top_p=1e-6, rng=jax.random.key(2),
            )
        )
        np.testing.assert_array_equal(greedy, p0)

    def test_top_k_restricts_support(self):
        # with top_k=2 every sampled token must be one of the 2 highest-
        # logit tokens of its actual decode distribution; verify via
        # teacher-forced re-scoring of the emitted sequence
        params, tokens, heads, _ = _setup()
        out = np.asarray(
            G.generate(
                params, jnp.asarray(tokens[:, :4]),
                n_heads=heads, max_new_tokens=8,
                temperature=1.3, top_k=2, rng=jax.random.key(3),
            )
        )
        from znicz_tpu.workflow.transformer import lm_apply

        full = np.asarray(lm_apply(params, jnp.asarray(out), n_heads=heads))
        for p in range(4, 12):
            top2 = np.argsort(full[:, p - 1], axis=-1)[:, -2:]
            for b in range(out.shape[0]):
                assert out[b, p] in top2[b], (b, p)

    def test_bad_truncation_args_rejected(self):
        params, tokens, heads, _ = _setup()
        with pytest.raises(ValueError, match="top_k"):
            G.generate(
                params, jnp.asarray(tokens[:, :4]),
                n_heads=heads, max_new_tokens=2,
                temperature=1.0, top_p=0.0, rng=jax.random.key(0),
            )

    def test_top_k_above_vocab_clamps_to_full_support(self):
        params, tokens, heads, vocab = _setup()
        out = np.asarray(
            G.generate(
                params, jnp.asarray(tokens[:, :4]),
                n_heads=heads, max_new_tokens=4,
                temperature=1.0, top_k=vocab + 30, rng=jax.random.key(1),
            )
        )
        ref = np.asarray(
            G.generate(
                params, jnp.asarray(tokens[:, :4]),
                n_heads=heads, max_new_tokens=4,
                temperature=1.0, rng=jax.random.key(1),
            )
        )
        np.testing.assert_array_equal(out, ref)

    def test_temperature_sweep_reuses_one_compile(self):
        # temperature/top_p are traced operands: distinct values must not
        # recompile the decode program
        params, tokens, heads, _ = _setup()
        prompt = jnp.asarray(tokens[:, :4])
        kw = dict(n_heads=heads, max_new_tokens=3, rng=jax.random.key(0))
        G.generate(params, prompt, temperature=0.7, top_p=0.9, **kw)
        n0 = G._generate_impl._cache_size()
        G.generate(params, prompt, temperature=1.3, top_p=0.8, **kw)
        assert G._generate_impl._cache_size() == n0


class TestServingDecode:
    """EOS and argument semantics the engine's reference must keep: EOS
    early-exit matches the full-budget run up to EOS."""

    def test_bucket_for_ladder(self):
        assert G.bucket_for(1, (16, 32)) == 16
        assert G.bucket_for(16, (16, 32)) == 16
        assert G.bucket_for(17, (16, 32)) == 32
        # past the top rung: keep doubling (geometric, never rejects)
        assert G.bucket_for(33, (16, 32)) == 64
        assert G.bucket_for(200, (16, 32)) == 256
        with pytest.raises(ValueError, match="positive"):
            G.bucket_for(0, (16,))

    def test_eos_early_exit_matches_full_budget_up_to_eos(self):
        # pick an EOS id the greedy run actually emits; rows must match
        # the full-budget run up to (and including) their first EOS and
        # emit EOS for the rest of the budget
        params, tokens, heads, _ = _setup()
        prompt = jnp.asarray(tokens[:, :4])
        ref = np.asarray(
            G.generate(params, prompt, n_heads=heads, max_new_tokens=8)
        )
        eos = int(ref[0, 4 + 2])
        out = np.asarray(
            G.generate(
                params, prompt, n_heads=heads, max_new_tokens=8,
                eos_id=eos,
            )
        )
        assert (out[:, :4] == np.asarray(prompt)).all()
        for b in range(out.shape[0]):
            new_ref, new_out = ref[b, 4:], out[b, 4:]
            hit = np.where(new_ref == eos)[0]
            k = hit[0] + 1 if len(hit) else len(new_ref)
            np.testing.assert_array_equal(new_out[:k], new_ref[:k])
            assert (new_out[k:] == eos).all()

    def test_zero_budget_rejected_with_clear_error(self):
        params, tokens, heads, _ = _setup()
        with pytest.raises(ValueError, match="max_new_tokens"):
            G.generate(
                params, tokens[:, :4], n_heads=heads, max_new_tokens=0
            )

    @pytest.mark.parametrize("through", ["generate", "engine"])
    def test_negative_temperature_rejected(self, through):
        # a negative temperature would sample the INVERTED distribution
        params, tokens, heads, _ = _setup()
        with pytest.raises(ValueError, match="temperature >= 0"):
            if through == "generate":
                G.generate(
                    params, tokens[:, :4], n_heads=heads,
                    max_new_tokens=2, temperature=-1.0,
                    rng=jax.random.key(0),
                )
            else:
                PagedDecodeEngine(
                    params, n_heads=heads, eos_id=0, temperature=-1.0,
                    rng=jax.random.key(0),
                )
