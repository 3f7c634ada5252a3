"""Seeded weights, made on the device in one jitted call per model, in
the type they are served or trained in (float32 masters).  The drivers
hand the SAME arrays to the program and to the plain reference, so the
reference takes nothing the program has made."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    """A key from any whole number: seeds a little over 2**31 do not fit
    the 32 signed bits ``jax.random.key`` takes."""
    seed = int(seed)
    return jax.random.fold_in(
        jax.random.key(seed & 0x7FFFFFFF), (seed >> 31) & 0x7FFFFFFF
    )


def cnn_shapes(cfg):
    """[(layer index, weight shape, bias shape, w_std)] for the conv and
    fc entries of a ``configs/*.json`` layer list (HWIO / [in, out])."""
    h, w, c = cfg["input_shape"]
    flat = None
    out = []
    for i, spec in enumerate(cfg["layers"]):
        kind = spec["type"]
        if kind == "conv":
            k, s, p, n = spec["k"], spec["stride"], spec["pad"], spec["n"]
            out.append((i, (k, k, c, n), (n,), spec["w_std"]))
            h = (h + 2 * p - k) // s + 1
            w = (w + 2 * p - k) // s + 1
            c = n
        elif kind == "max_pool":
            h = (h - spec["k"]) // spec["stride"] + 1
            w = (w - spec["k"]) // spec["stride"] + 1
        elif kind == "fc":
            n_in = flat if flat is not None else h * w * c
            out.append((i, (n_in, spec["n"]), (spec["n"],), spec["w_std"]))
            flat = spec["n"]
    return out


def cnn_weights(cfg, seed: int):
    """One dict per layer of the list, ``{}`` where a layer has no
    parameters, else ``{"weights", "bias"}``: gaussian weights at the
    layer's ``w_std``, biases uniform in [-w_std, w_std]."""
    shapes = cnn_shapes(cfg)
    n_layers = len(cfg["layers"])

    @jax.jit
    def make(key):
        params = [{} for _ in range(n_layers)]
        keys = jax.random.split(key, 2 * len(shapes))
        for j, (i, w_shape, b_shape, std) in enumerate(shapes):
            params[i] = {
                "weights": std
                * jax.random.normal(keys[2 * j], w_shape, jnp.float32),
                "bias": jax.random.uniform(
                    keys[2 * j + 1], b_shape, jnp.float32, -std, std
                ),
            }
        return params

    return make(jax.random.fold_in(seed_key(seed), 1))


def lm_weights(cfg, seed: int):
    """``{"embed", "pos", "head", "blocks": [...]}`` at the
    configuration's sizes; see ``assumed.weights`` in the file."""
    d, n_layer = cfg["n_embd"], cfg["n_layer"]
    v, n_pos, d_ff = cfg["vocab_size"], cfg["n_positions"], cfg["n_inner"]
    std = 1.0 / d**0.5

    @jax.jit
    def make(key):
        def normal(k, shape, s):
            return s * jax.random.normal(k, shape, jnp.float32)

        keys = jax.random.split(key, 3 + n_layer)
        blocks = []
        for layer in range(n_layer):
            bk = jax.random.split(keys[3 + layer], 6)
            blocks.append(
                {
                    "ln1_scale": jnp.ones((d,), jnp.float32),
                    "ln1_bias": jnp.zeros((d,), jnp.float32),
                    "ln2_scale": jnp.ones((d,), jnp.float32),
                    "ln2_bias": jnp.zeros((d,), jnp.float32),
                    "wq": normal(bk[0], (d, d), std),
                    "wk": normal(bk[1], (d, d), std),
                    "wv": normal(bk[2], (d, d), std),
                    "wo": normal(bk[3], (d, d), std),
                    "w_up": normal(bk[4], (d, d_ff), std),
                    "up_bias": jnp.zeros((d_ff,), jnp.float32),
                    "w_down": normal(bk[5], (d_ff, d), 1.0 / d_ff**0.5),
                    "down_bias": jnp.zeros((d,), jnp.float32),
                }
            )
        return {
            "embed": normal(keys[0], (v, d), std),
            "pos": normal(keys[1], (n_pos, d), std),
            "head": normal(keys[2], (d, v), std),
            "blocks": blocks,
        }

    return make(jax.random.fold_in(seed_key(seed), 2))
