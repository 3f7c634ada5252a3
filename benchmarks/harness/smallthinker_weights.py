"""Seeded weights of the ``smallthinker-21b-l8`` configuration, drawn ON
THE DEVICE leaf by leaf in the type they are served in (bfloat16; norm
gains float32): 4.0 billion values are not drawn on the host, and one leaf
at a time keeps the float32 draw of the largest (the embedding or the
head, 151936 x 2560: 1.6 GB) the only temporary.  The driver hands the
SAME arrays to the program and to the plain reference.

``assumed.weights``: gaussian, std ``fan_in ** -0.5`` (the embedding:
``hidden_size ** -0.5``), every norm gain 1."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from harness.weights import seed_key


def leaf_shapes(cfg) -> dict:
    """``{"embed", "final_norm", "head", "blocks": [{leaf: (shape, fan_in
    or None)}]}``: every leaf held here.  A ``None`` fan-in marks a norm
    gain."""
    d, dim = cfg["hidden_size"], cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * dim, cfg["num_key_value_heads"] * dim
    experts, f = cfg["moe_num_primary_experts"], cfg["moe_ffn_hidden_size"]
    block = {
        "attn_norm": ((d,), None), "wq": ((d, q), d), "wk": ((d, kv), d),
        "wv": ((d, kv), d), "wo": ((q, d), q), "ffn_norm": ((d,), None),
        "router": ((d, experts), d),
        "experts_gate": ((experts, d, f), d), "experts_up": ((experts, d, f), d),
        "experts_down": ((experts, f, d), f),
    }
    vocab = cfg["vocab_size"]
    return {
        "embed": ((vocab, d), d), "final_norm": ((d,), None),
        "head": ((d, vocab), d),
        "blocks": [dict(block) for _ in range(cfg["num_hidden_layers"])],
    }


@functools.partial(jax.jit, static_argnames=("shape", "std"))
def _normal(key, shape, std):
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(jnp.bfloat16)


def weights(cfg, seed: int) -> dict:
    """The tree the reference reads; ``program_tree`` turns it into the
    list the program's engine takes."""
    key = jax.random.fold_in(seed_key(seed), 5)
    counter = iter(range(1 << 20))

    def draw(spec):
        shape, fan_in = spec
        if fan_in is None:
            return jnp.ones(shape, jnp.float32)
        return _normal(
            jax.random.fold_in(key, next(counter)), shape, float(fan_in) ** -0.5
        )

    shapes = leaf_shapes(cfg)
    return {
        "embed": draw(shapes["embed"]),
        "blocks": [
            {name: draw(spec) for name, spec in block.items()}
            for block in shapes["blocks"]
        ],
        "final_norm": draw(shapes["final_norm"]),
        "head": draw(shapes["head"]),
    }


def program_tree(w: dict) -> list:
    """``[{"embed"}, block_0 .. block_L-1, {"final_norm", "head"}]``, the
    arrays shared, not copied."""
    return (
        [{"embed": w["embed"]}] + [dict(b) for b in w["blocks"]]
        + [{"final_norm": w["final_norm"], "head": w["head"]}]
    )


def n_parameters(cfg) -> int:
    """Every matrix held here (norm gains not counted, as in the
    configuration's ``parameters_held``)."""
    shapes = leaf_shapes(cfg)
    leaves = [shapes["embed"], shapes["head"]] + [
        spec for block in shapes["blocks"] for spec in block.values()
    ]
    total = 0
    for shape, fan_in in leaves:
        n = 1
        for dim in shape:
            n *= dim
        total += n if fan_in is not None else 0
    return total
