"""Seeded weights of the ``laguna-xs2-stage1`` configuration, drawn ON THE
DEVICE leaf by leaf in the type they are served in (bfloat16; norm gains and
the router's choice bias float32): 5.6 billion values are not drawn on the
host, and one leaf at a time keeps the float32 draw of the largest (a
layer's 256 x 2048 x 512 expert matrices, 1.1 GB) the only temporary.  The
driver hands the SAME arrays to the program and to the plain reference.

``assumed.weights``: gaussian, std ``fan_in ** -0.5`` times the leaf's entry
in ``assumed.gains`` (1 where it has none; the embedding:
``assumed.embed_std``), every norm gain 1, the router's choice bias 0.  The
head's column of ``assumed.eos_id`` is ZERO (``assumed.eos_column``), so an
answer ends at its budget and never at a chance end-of-sequence."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from harness.dots3_weights import _normal
from harness.smallthinker_weights import program_tree  # noqa: F401  (the same tree)
from harness.weights import seed_key

FULL = "full_attention"


def leaf_shapes(cfg) -> dict:
    """``{"embed", "final_norm", "head", "blocks": [{leaf: (shape, fan_in
    or None)}]}``: every leaf held here.  A ``None`` fan-in marks a float32
    vector (a norm gain or the choice bias)."""
    d, dim = cfg["hidden_size"], cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * dim
    experts, f = cfg["num_experts"], cfg["moe_intermediate_size"]
    shared, dense = cfg["shared_expert_intermediate_size"], cfg["intermediate_size"]
    blocks = []
    for layer in range(cfg["num_hidden_layers"]):
        heads = cfg["num_attention_heads_per_layer"][layer]
        block = {
            "attn_norm": ((d,), None), "wq": ((d, heads * dim), d),
            "wk": ((d, kv), d), "wv": ((d, kv), d), "wg": ((d, heads), d),
            "wo": ((heads * dim, d), heads * dim), "ffn_norm": ((d,), None),
        }
        if cfg["mlp_layer_types"][layer] == "dense":
            block.update(
                w_gate=((d, dense), d), w_up=((d, dense), d),
                w_down=((dense, d), dense),
            )
        else:
            block.update(
                router=((d, experts), d), router_bias=((experts,), None),
                experts_gate=((experts, d, f), d), experts_up=((experts, d, f), d),
                experts_down=((experts, f, d), f),
                shared_gate=((d, shared), d), shared_up=((d, shared), d),
                shared_down=((shared, d), shared),
            )
        blocks.append(block)
    vocab = cfg["vocab_size"]
    return {
        "embed": ((vocab, d), d), "final_norm": ((d,), None),
        "head": ((d, vocab), d), "blocks": blocks,
    }


def weights(cfg, seed: int) -> dict:
    """The tree the reference reads; ``program_tree`` turns it into the
    list the program's engine takes."""
    key = jax.random.fold_in(seed_key(seed), 13)
    counter = iter(range(1 << 20))
    assumed = cfg["assumed"]

    def draw(name, spec):
        shape, fan_in = spec
        at = jax.random.fold_in(key, next(counter))
        if fan_in is None:  # a norm gain, or the choice bias
            return jnp.full(shape, 0.0 if name == "router_bias" else 1.0, jnp.float32)
        std = float(assumed["gains"].get(name, 1.0)) * float(fan_in) ** -0.5
        if name == "embed":
            std = float(assumed["embed_std"])
        return _normal(at, shape, std, jnp.bfloat16)

    shapes = leaf_shapes(cfg)
    return {
        "embed": draw("embed", shapes["embed"]),
        "blocks": [
            {name: draw(name, spec) for name, spec in block.items()}
            for block in shapes["blocks"]
        ],
        "final_norm": draw("final_norm", shapes["final_norm"]),
        # assumed.eos_column: no greedy token is the end-of-sequence id
        "head": draw("head", shapes["head"]).at[:, assumed["eos_id"]].set(0),
    }


def n_parameters(cfg) -> int:
    """Every matrix held here (norm gains and the choice bias not counted,
    as in the configuration's ``parameters_held``)."""
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
