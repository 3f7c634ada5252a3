"""The plain references against the system, at toy size on the CPU: the
same seeded weights through the program's own forward/backward code and
through ``benchmarks/reference`` must agree to float32 rounding."""

import jax
import jax.numpy as jnp
import numpy as np

import toy
from drivers_access import serve, train
from harness import weights
from harness.loading import load_module


def test_lm_reference_is_the_programs_forward_pass():
    from znicz_tpu.workflow.transformer import lm_apply

    cfg = toy.lm_config()
    w = weights.lm_weights(cfg, 11)
    tokens = np.random.default_rng(0).integers(1, cfg["vocab_size"], 50)
    want = lm_apply(
        serve.program_tree(w), jnp.asarray(tokens, jnp.int32)[None],
        n_heads=cfg["n_head"],
    )[0]
    got = load_module("reference", "lm").logits(cfg, w, tokens)
    # a transcription slip (a missing residual, gelu for tanh) moves
    # logits by their own size, ~1; rounding by ~1e-5
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-4)


def test_lm_served_gaps_are_zero_for_the_greedy_token_only():
    ref = load_module("reference", "lm")
    logits = jnp.asarray([[0.0, 3.0, 1.0], [2.0, 0.0, 5.0], [1.0, 1.5, 0.0]])
    # prompt of 2 tokens: rows 1 and 2 judge the two served tokens
    gaps = ref.served_gaps(logits, 2, [2, 0])
    np.testing.assert_allclose(gaps, [0.0, 0.5])


def test_cnn_reference_is_the_programs_loss_and_gradients():
    from znicz_tpu.nn import evaluator
    from znicz_tpu.workflow import model as model_mod

    cfg = toy.cnn_config()
    params = weights.cnn_weights(cfg, 5)
    model = model_mod.build(toy.program_layers(cfg), cfg["input_shape"])
    rng = np.random.default_rng(1)
    x = rng.integers(0, 256, (6, *cfg["input_shape"]), dtype=np.uint8)
    y = rng.integers(0, cfg["n_classes"], (6,)).astype(np.int32)
    key = jax.random.key(7)

    def program_loss(p):
        xf = jnp.asarray(x).astype(jnp.float32) * (1.0 / 255.0) - 0.5
        out = model.apply(p, xf, train=True, rng=jax.random.fold_in(key, 0))
        return evaluator.softmax(out, jnp.asarray(y))["loss"]

    want_loss, want_grads = jax.value_and_grad(program_loss)(params)
    masks = train.dropout_masks(cfg, key, 0, 6)
    got_loss, got_grads = load_module("reference", "alexnet").loss_and_grads(
        cfg, params, jnp.asarray(x), jnp.asarray(y), masks, rows=4
    )
    np.testing.assert_allclose(got_loss, want_loss, rtol=1e-5)
    for got, want in zip(got_grads, want_grads):
        for name in want:
            scale = float(jnp.max(jnp.abs(want[name]))) or 1.0
            np.testing.assert_allclose(
                got[name], want[name], rtol=0, atol=2e-4 * scale
            )


def test_cnn_reference_step_is_the_programs_update_rule():
    from znicz_tpu.nn import optimizer

    cfg = toy.cnn_config()
    params = weights.cnn_weights(cfg, 5)
    grads = jax.tree_util.tree_map(lambda a: 0.1 * jnp.cos(a), params)
    velocity = jax.tree_util.tree_map(lambda a: 0.01 * jnp.sin(a), params)
    hyper = optimizer.HyperParams(**cfg["optimizer"])
    want_p, want_v = optimizer.update(params, grads, velocity, hyper)
    got_p, got_v = load_module("reference", "alexnet").sgd_step(
        cfg, params, velocity, grads
    )
    for got, want in zip(got_p + got_v, list(want_p) + list(want_v)):
        for name in want:
            np.testing.assert_allclose(got[name], want[name], rtol=1e-6, atol=1e-9)
