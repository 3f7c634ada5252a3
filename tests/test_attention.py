"""Attention + ring-attention sequence parallelism tests (8-device mesh)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from znicz_tpu.ops import attention
from znicz_tpu.parallel import make_mesh
from znicz_tpu.parallel.ring_attention import ring_attention


def _qkv(b=2, t=32, h=4, d=16, seed=0):
    keys = jax.random.split(jax.random.key(seed), 3)
    shape = (b, t, h, d)
    return tuple(jax.random.normal(k, shape, jnp.float32) for k in keys)


class TestDotProductAttention:
    def test_softmax_rows_sum_to_one_effect(self):
        q, k, v = _qkv()
        ones = jnp.ones_like(v)
        out = attention.dot_product_attention(q, k, ones)
        np.testing.assert_allclose(out, 1.0, rtol=1e-5)

    def test_causal_first_token_attends_self_only(self):
        q, k, v = _qkv()
        out = attention.dot_product_attention(q, k, v, causal=True)
        np.testing.assert_allclose(
            out[:, 0], v[:, 0], rtol=1e-5, atol=1e-6
        )

    def test_mha_shapes(self):
        from znicz_tpu.core import prng

        prng.seed_all(3)
        params = attention.init_mha_params(32, 4)
        x = jax.random.normal(jax.random.key(1), (2, 10, 32))
        y = attention.mha(params, x, n_heads=4)
        assert y.shape == (2, 10, 32)


class TestPagedAttention:
    """Block-table attention (docs/SERVING.md paged KV): gathered-window
    numerics must equal a dense masked softmax over the same keys,
    whatever (shuffled) block assignment the table holds."""

    @pytest.fixture(params=["heads_split", "merged"])
    def stored(self, request):
        """Puts a pool the tests build as ``[n_blocks, bs, H, D]`` on the
        device in the layout under test: ``merged`` is the
        ``[n_blocks, bs, H*D]`` that ``init_paged_kv`` builds and the
        engine writes; ``heads_split`` is the rank the direct callers
        below always passed."""
        if request.param == "merged":
            return lambda pool: jnp.asarray(
                pool.reshape(*pool.shape[:2], -1)
            )
        return jnp.asarray

    def _paged_setup(self, b=2, t=32, h=2, d=8, bs=8, seed=0):
        rng = np.random.default_rng(seed)
        m = t // bs
        k = rng.normal(size=(b, t, h, d)).astype(np.float32)
        v = rng.normal(size=(b, t, h, d)).astype(np.float32)
        # scatter each row's contiguous K/V into a shared pool under a
        # SHUFFLED block assignment (block 0 reserved null, as served)
        n_blocks = 1 + b * m
        table = (
            rng.permutation(np.arange(1, n_blocks))
            .reshape(b, m)
            .astype(np.int32)
        )
        k_pool = np.zeros((n_blocks, bs, h, d), np.float32)
        v_pool = np.zeros((n_blocks, bs, h, d), np.float32)
        for row in range(b):
            for j in range(m):
                k_pool[table[row, j]] = k[row, j * bs:(j + 1) * bs]
                v_pool[table[row, j]] = v[row, j * bs:(j + 1) * bs]
        return k, v, k_pool, v_pool, table

    @staticmethod
    def _dense_ref(q, k, v, q_pos):
        """Masked stable softmax per row, numpy — the paged contract."""
        b, tq, h, d = q.shape
        out = np.zeros_like(q)
        for row in range(b):
            for qi in range(tq):
                p = q_pos[row, qi]
                s = np.einsum(
                    "hd,khd->hk", q[row, qi], k[row]
                ) / np.sqrt(d)
                mask = np.zeros(k.shape[1], bool)
                mask[: p + 1] = True
                s = np.where(mask[None, :], s, -np.inf)
                e = np.exp(s - s.max(axis=-1, keepdims=True))
                w = e / e.sum(axis=-1, keepdims=True)
                out[row, qi] = np.einsum("hk,khd->hd", w, v[row])
        return out

    def test_decode_step_matches_dense_masked_softmax(self, stored):
        bs = 8
        k, v, k_pool, v_pool, table = self._paged_setup(bs=bs)
        rng = np.random.default_rng(1)
        q = rng.normal(size=(2, 1, 2, 8)).astype(np.float32)
        pos = np.asarray([[13], [29]], np.int32)
        out = attention.paged_attention(
            jnp.asarray(q), stored(k_pool), stored(v_pool),
            jnp.asarray(table), jnp.asarray(pos), block_size=bs,
        )
        ref = self._dense_ref(q, k, v, pos)
        np.testing.assert_allclose(
            np.asarray(out), ref, rtol=2e-5, atol=2e-6
        )

    def test_prefill_chunk_queries_match(self, stored):
        # a whole chunk of queries at consecutive positions (the
        # chunked-prefill shape)
        bs = 8
        k, v, k_pool, v_pool, table = self._paged_setup(bs=bs)
        rng = np.random.default_rng(2)
        q = rng.normal(size=(2, bs, 2, 8)).astype(np.float32)
        q_pos = np.broadcast_to(np.arange(bs), (2, bs)).astype(np.int32)
        out = attention.paged_attention(
            jnp.asarray(q), stored(k_pool), stored(v_pool),
            jnp.asarray(table), jnp.asarray(q_pos), block_size=bs,
        )
        ref = self._dense_ref(q, k, v, q_pos)
        assert np.isfinite(np.asarray(out)).all()
        np.testing.assert_allclose(
            np.asarray(out), ref, rtol=2e-5, atol=2e-6
        )

    def test_stale_blocks_cannot_leak(self, stored):
        # poison every pool block the tables do NOT cover a row's valid
        # window with: garbage past pos / outside the table must not
        # change the output (masking is by index, never by value)
        bs = 8
        k, v, k_pool, v_pool, table = self._paged_setup(bs=bs)
        rng = np.random.default_rng(3)
        q = rng.normal(size=(2, 1, 2, 8)).astype(np.float32)
        pos = np.asarray([[10], [3]], np.int32)
        clean = attention.paged_attention(
            jnp.asarray(q), stored(k_pool), stored(v_pool),
            jnp.asarray(table), jnp.asarray(pos), block_size=bs,
        )
        kp, vp = k_pool.copy(), v_pool.copy()
        for row in range(2):
            p = int(pos[row, 0])
            jb, slot = p // bs, p % bs
            kp[table[row, jb], slot + 1:] = 1e9  # rest of the live block
            vp[table[row, jb], slot + 1:] = 1e9
            for j in range(jb + 1, table.shape[1]):  # blocks past pos
                kp[table[row, j]] = 1e9
                vp[table[row, j]] = 1e9
        kp[0] = 1e9  # the null block
        vp[0] = 1e9
        poisoned = attention.paged_attention(
            jnp.asarray(q), stored(kp), stored(vp),
            jnp.asarray(table), jnp.asarray(pos), block_size=bs,
        )
        np.testing.assert_allclose(
            np.asarray(clean), np.asarray(poisoned), rtol=1e-6
        )

    def test_many_tables_one_block_aliasing(self, stored):
        # prefix sharing maps ONE physical block into MANY tables: each
        # row's output must equal the dense reference over the content
        # its own table resolves to — the gather must not care that a
        # block id repeats across rows
        bs = 8
        rng = np.random.default_rng(5)
        b, t, h, d = 3, 24, 2, 8
        m = t // bs
        shared = rng.normal(size=(bs, h, d)).astype(np.float32)
        shared_v = rng.normal(size=(bs, h, d)).astype(np.float32)
        k = rng.normal(size=(b, t, h, d)).astype(np.float32)
        v = rng.normal(size=(b, t, h, d)).astype(np.float32)
        # every row's FIRST block is the shared prefix content
        k[:, :bs] = shared
        v[:, :bs] = shared_v
        # pool: block 1 = the one shared block; per-row private tails
        n_blocks = 2 + b * (m - 1)
        k_pool = np.zeros((n_blocks, bs, h, d), np.float32)
        v_pool = np.zeros((n_blocks, bs, h, d), np.float32)
        k_pool[1], v_pool[1] = shared, shared_v
        table = np.zeros((b, m), np.int32)
        table[:, 0] = 1  # ALIASED: all three tables point at block 1
        nxt = 2
        for row in range(b):
            for j in range(1, m):
                table[row, j] = nxt
                k_pool[nxt] = k[row, j * bs:(j + 1) * bs]
                v_pool[nxt] = v[row, j * bs:(j + 1) * bs]
                nxt += 1
        rngq = np.random.default_rng(6)
        q = rngq.normal(size=(b, 1, h, d)).astype(np.float32)
        # rows at DIFFERENT depths through the same shared block: row 0
        # still inside it, rows 1/2 past it
        pos = np.asarray([[5], [13], [21]], np.int32)
        out = attention.paged_attention(
            jnp.asarray(q), stored(k_pool), stored(v_pool),
            jnp.asarray(table), jnp.asarray(pos), block_size=bs,
        )
        ref = self._dense_ref(q, k, v, pos)
        np.testing.assert_allclose(
            np.asarray(out), ref, rtol=2e-5, atol=2e-6
        )

    def test_aliased_block_validity_is_per_row(self, stored):
        # poison-grade check for aliasing: positions of the SHARED
        # block past a shallow row's pos are real live content for a
        # deeper row.  Perturbing them must leave the shallow row's
        # output bit-identical (masked by index) while changing the
        # deeper row's (it genuinely attends them).
        bs = 8
        rng = np.random.default_rng(7)
        h, d = 2, 8
        shared_k = rng.normal(size=(bs, h, d)).astype(np.float32)
        shared_v = rng.normal(size=(bs, h, d)).astype(np.float32)
        k_pool = np.zeros((3, bs, h, d), np.float32)
        v_pool = np.zeros((3, bs, h, d), np.float32)
        k_pool[1], v_pool[1] = shared_k, shared_v
        k_pool[2] = rng.normal(size=(bs, h, d)).astype(np.float32)
        v_pool[2] = rng.normal(size=(bs, h, d)).astype(np.float32)
        table = np.asarray([[1, 0], [1, 2]], np.int32)
        q = rng.normal(size=(2, 1, h, d)).astype(np.float32)
        pos = np.asarray([[3], [11]], np.int32)  # row 0 shallow, row 1 deep

        def run(kp, vp):
            return np.asarray(
                attention.paged_attention(
                    jnp.asarray(q), stored(kp), stored(vp),
                    jnp.asarray(table), jnp.asarray(pos), block_size=bs,
                )
            )

        clean = run(k_pool, v_pool)
        kp, vp = k_pool.copy(), v_pool.copy()
        kp[1, 5:] += 3.0  # rewrite shared-block positions 5..7
        vp[1, 5:] += 3.0
        pert = run(kp, vp)
        np.testing.assert_array_equal(clean[0], pert[0])  # masked out
        assert np.abs(clean[1] - pert[1]).max() > 1e-6  # really attended

    def test_engine_pools_store_heads_merged(self):
        # the stored layout is [n_blocks, block_size, H*hd] (why:
        # tests/test_paged_layout_aot.py); the bytes a block and the
        # pool are reported to take are those of the K/V they hold
        from znicz_tpu.services.engine import PagedDecodeEngine
        from znicz_tpu.workflow.generate import init_paged_kv
        from znicz_tpu.workflow.transformer import init_lm_params

        layers, heads, head_dim, bs = 2, 4, 8, 16
        params = init_lm_params(50, heads * head_dim, layers, heads, 64)
        pools = init_paged_kv(params, 9, bs)
        assert len(pools) == layers
        for pool in pools:
            assert pool["k"].shape == pool["v"].shape == (
                9, bs, heads * head_dim
            )
            assert pool["k"].dtype == params[1]["wq"].dtype
        eng = PagedDecodeEngine(
            params, n_heads=heads, eos_id=0, batch_size=2, max_seq=64,
            block_size=bs,
        )
        assert [p["k"].shape for p in eng._pools] == [
            (2 * 4 + 1, bs, heads * head_dim)
        ] * layers
        per_block = layers * 2 * bs * heads * head_dim * 4
        assert eng.block_bytes == per_block
        stats = eng.stats()
        assert stats["block_bytes"] == per_block
        assert stats["pool_bytes"] == 2 * 4 * per_block


class TestRingAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_single_device(self, causal):
        mesh = make_mesh(8, 1)
        q, k, v = _qkv(b=2, t=64, h=4, d=16, seed=7)
        ref = attention.dot_product_attention(q, k, v, causal=causal)
        out = ring_attention(q, k, v, mesh=mesh, causal=causal)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-6
        )

    def test_long_sequence_grad_flows(self):
        mesh = make_mesh(8, 1)
        q, k, v = _qkv(b=1, t=128, h=2, d=8, seed=9)

        def loss(q, k, v):
            return jnp.sum(
                jnp.square(ring_attention(q, k, v, mesh=mesh, causal=True))
            )

        g = jax.grad(loss)(q, k, v)
        ref_g = jax.grad(
            lambda q, k, v: jnp.sum(
                jnp.square(
                    attention.dot_product_attention(q, k, v, causal=True)
                )
            )
        )(q, k, v)
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(ref_g), rtol=1e-4, atol=1e-5
        )

    def test_under_jit_with_sharded_inputs(self):
        mesh = make_mesh(8, 1)
        from jax.sharding import NamedSharding, PartitionSpec as P

        q, k, v = _qkv(b=2, t=64, h=4, d=16, seed=11)
        sharding = NamedSharding(mesh, P(None, "data", None, None))
        qs, ks, vs = (jax.device_put(a, sharding) for a in (q, k, v))
        f = jax.jit(
            lambda q, k, v: ring_attention(q, k, v, mesh=mesh, causal=True)
        )
        out = f(qs, ks, vs)
        ref = attention.dot_product_attention(q, k, v, causal=True)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-6
        )


class TestFlashLse:
    def test_lse_matches_dense_logsumexp(self):
        from znicz_tpu.ops.pallas.attention import flash_attention_lse

        q, k, v = _qkv(b=1, t=48, h=2, d=16, seed=3)
        out, lse = flash_attention_lse(q, k, v, causal=True)
        ref = attention.dot_product_attention(q, k, v, causal=True)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-6
        )
        # reference logsumexp over the causal score rows
        scale = 1.0 / np.sqrt(q.shape[-1])
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
        t = q.shape[1]
        mask = np.tril(np.ones((t, t), bool))
        s = jnp.where(mask[None, None], s, -jnp.inf)
        ref_lse = jax.scipy.special.logsumexp(s, axis=-1)  # [B, H, T]
        np.testing.assert_allclose(
            np.asarray(lse),
            np.asarray(ref_lse.transpose(0, 2, 1)),
            rtol=1e-5, atol=1e-5,
        )

    def test_lse_gradient_flows(self):
        """The lse OUTPUT must carry gradient (ring combination uses it)."""
        from znicz_tpu.ops.pallas.attention import flash_attention_lse

        q, k, v = _qkv(b=1, t=32, h=2, d=8, seed=5)

        def loss(q, k, v):
            out, lse = flash_attention_lse(q, k, v, causal=True)
            return jnp.sum(jnp.square(out)) + jnp.sum(jnp.square(lse))

        def ref_loss(q, k, v):
            scale = 1.0 / np.sqrt(q.shape[-1])
            s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
            t = q.shape[1]
            mask = np.tril(np.ones((t, t), bool))
            s = jnp.where(mask[None, None], s, -jnp.inf)
            p = jax.nn.softmax(s, axis=-1)
            out = jnp.einsum("bhqk,bkhd->bqhd", p, v)
            lse = jax.scipy.special.logsumexp(s, axis=-1)
            return jnp.sum(jnp.square(out)) + jnp.sum(jnp.square(lse))

        g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        rg = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g, rg):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4
            )


class TestRingFlashInner:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_dense_inner(self, causal):
        mesh = make_mesh(8, 1)
        q, k, v = _qkv(b=2, t=64, h=4, d=16, seed=13)
        ref = ring_attention(q, k, v, mesh=mesh, causal=causal)
        out = ring_attention(
            q, k, v, mesh=mesh, causal=causal, inner="flash"
        )
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-6
        )

    def test_grads_match_single_device(self):
        mesh = make_mesh(8, 1)
        q, k, v = _qkv(b=1, t=64, h=2, d=8, seed=17)

        def loss(fn):
            return lambda q, k, v: jnp.sum(jnp.square(fn(q, k, v)))

        g = jax.grad(
            loss(
                lambda q, k, v: ring_attention(
                    q, k, v, mesh=mesh, causal=True, inner="flash"
                )
            ),
            argnums=(0, 1, 2),
        )(q, k, v)
        rg = jax.grad(
            loss(
                lambda q, k, v: attention.dot_product_attention(
                    q, k, v, causal=True
                )
            ),
            argnums=(0, 1, 2),
        )(q, k, v)
        for a, b in zip(g, rg):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5
            )

    def test_bf16_inputs_causal(self):
        # the causal lax.switch branches must agree on dtype (skip branch
        # emits f32 zeros) — regression for a trace-time TypeError
        mesh = make_mesh(8, 1)
        q, k, v = (
            x.astype(jnp.bfloat16)
            for x in _qkv(b=1, t=64, h=2, d=8, seed=19)
        )
        out = ring_attention(q, k, v, mesh=mesh, causal=True, inner="flash")
        assert out.dtype == jnp.bfloat16
        ref = ring_attention(q, k, v, mesh=mesh, causal=True, inner="dense")
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            rtol=5e-2, atol=5e-2,
        )

    def test_bad_inner_rejected(self):
        mesh = make_mesh(8, 1)
        q, k, v = _qkv(b=1, t=16, h=1, d=8)
        with pytest.raises(ValueError, match="inner"):
            ring_attention(q, k, v, mesh=mesh, inner="blockwise")


class TestBf16FlashKernel:
    def test_bf16_flash_matches_f32_twin(self):
        # the kernel keeps input dtype on the MXU; bf16 q/k/v must still
        # reproduce the f32 jnp twin within bf16 mantissa tolerance
        from znicz_tpu.ops.pallas.attention import flash_attention

        q, k, v = _qkv(b=2, t=128, h=2, d=32, seed=7)
        ref = attention.dot_product_attention(q, k, v, causal=True)
        out = flash_attention(
            q.astype(jnp.bfloat16),
            k.astype(jnp.bfloat16),
            v.astype(jnp.bfloat16),
            causal=True,
        )
        assert out.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref),
            rtol=3e-2, atol=3e-2,
        )

    def test_bf16_flash_grads_close_to_f32(self):
        from znicz_tpu.ops.pallas.attention import flash_attention

        q, k, v = _qkv(b=1, t=64, h=2, d=16, seed=9)

        def loss(fn, qkv):
            return jnp.sum(
                jnp.square(fn(*qkv, causal=True).astype(jnp.float32))
            )

        g_ref = jax.grad(
            lambda t: loss(attention.dot_product_attention, t)
        )((q, k, v))
        g_bf = jax.grad(lambda t: loss(flash_attention, t))(
            tuple(x.astype(jnp.bfloat16) for x in (q, k, v))
        )
        for a, b in zip(g_ref, g_bf):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b, np.float32),
                rtol=6e-2, atol=6e-2,
            )


class TestAttentionDtypeKnob:
    def test_bf16_attention_trains_close_to_f32(self):
        from znicz_tpu.core import prng
        from znicz_tpu.loader.fullbatch import FullBatchLoader
        from znicz_tpu.workflow.transformer import TransformerLMWorkflow

        tokens = np.random.default_rng(3).integers(
            0, 16, (32, 64)
        ).astype(np.int32)

        def run(dtype):
            prng.seed_all(61)
            ld = FullBatchLoader({"train": tokens.copy()}, minibatch_size=16)
            wf = TransformerLMWorkflow(
                ld, vocab=16, d_model=32, n_layers=2, n_heads=2,
                max_epochs=2, attention="flash", attention_dtype=dtype,
            )
            wf.initialize(seed=61)
            return [h["train"]["loss"] for h in wf.run().history]

        f32 = run("f32")
        bf16 = run("bf16")
        np.testing.assert_allclose(f32, bf16, rtol=2e-2)

    def test_invalid_attention_dtype_rejected(self):
        from znicz_tpu.loader.fullbatch import FullBatchLoader
        from znicz_tpu.workflow.transformer import TransformerLMWorkflow

        tokens = np.zeros((8, 16), np.int32)
        ld = FullBatchLoader({"train": tokens}, minibatch_size=4)
        with pytest.raises(ValueError, match="attention_dtype"):
            TransformerLMWorkflow(
                ld, vocab=4, attention_dtype="fp8"
            )

    def test_bf16_attention_composes_with_sequence_parallel(self):
        # attention_dtype wraps the ring-attention path too: bf16 q/k/v
        # through the ring (flash inner) must train close to the f32 run
        from znicz_tpu.core import prng
        from znicz_tpu.loader.fullbatch import FullBatchLoader
        from znicz_tpu.parallel import DataParallel, make_mesh
        from znicz_tpu.workflow.transformer import TransformerLMWorkflow

        tokens = np.random.default_rng(5).integers(
            0, 16, (32, 64)
        ).astype(np.int32)
        mesh = make_mesh(8, 1)

        def run(dtype):
            prng.seed_all(67)
            ld = FullBatchLoader({"train": tokens.copy()}, minibatch_size=16)
            wf = TransformerLMWorkflow(
                ld, vocab=16, d_model=32, n_layers=2, n_heads=2,
                max_epochs=2, sequence_parallel=True, mesh=mesh,
                parallel=DataParallel(mesh), attention_dtype=dtype,
                # force the flash inner (auto resolves dense on the CPU
                # test backend) so bf16 x SP x flash is really exercised
                attention="flash",
            )
            wf.initialize(seed=67)
            return [h["train"]["loss"] for h in wf.run().history]

        f32 = run("f32")
        bf16 = run("bf16")
        np.testing.assert_allclose(f32, bf16, rtol=2e-2)
