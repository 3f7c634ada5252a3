"""Seeded weights of the ``keye-vl2-30b-a3b-l6`` configuration, drawn ON THE
DEVICE leaf by leaf in the type they are served in (bfloat16; norm gains and
the indexer's key bias float32): 4.4 billion values are not drawn on the
host, and one leaf at a time keeps the float32 draw of the largest (a
layer's 128 x 2048 x 768 expert matrices, 0.8 GB) the only temporary.  The
driver hands the SAME arrays to the program and to the plain reference.

``assumed.weights``: gaussian, std ``fan_in ** -0.5`` times the leaf's entry
in ``assumed.gains`` (1 where it has none; the embedding:
``assumed.embed_std``), every norm gain 1 except ``q_norm``'s, which is
``assumed.q_norm_gain``: q and k leave their per-head norms with unit values,
so a score ``q . k 128^-0.5`` spreads by the gain over the keys, and at 3
WHICH keys are attended decides a head's output without one key deciding it
alone.  ``embed_std`` 1 with ``wo`` at 0.3 and ``experts_down`` at 0.5 keep a
token's own embedding as large as the six layers' updates together, so that
one layer's rounding does not grow through the five selections after it
(README.keye.md).  The indexer's key bias is a gaussian of std
``assumed.k_idx_bias_std``.  The head's column of ``assumed.eos_id`` is ZERO
(``assumed.eos_column``), so an answer ends at its budget and never at a
chance end-of-sequence."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from harness.dots3_weights import _normal
from harness.smallthinker_weights import program_tree  # noqa: F401  (the same tree)
from harness.weights import seed_key


def leaf_shapes(cfg) -> dict:
    """``{"embed", "final_norm", "head", "blocks": [{leaf: (shape, fan_in
    or None)}]}``: every leaf this chip holds.  A ``None`` fan-in marks a
    float32 vector (a norm gain or a bias)."""
    d, sa = cfg["hidden_size"], cfg["sa_config"]
    heads, groups, dim = (
        cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    )
    j, d_i = sa["indexer_num_heads"], sa["indexer_head_dim"]
    experts, f = cfg["num_experts"], cfg["moe_intermediate_size"]
    block = {
        "attn_norm": ((d,), None), "wq": ((d, heads * dim), d),
        "wk": ((d, groups * dim), d), "wv": ((d, groups * dim), d),
        "q_norm": ((dim,), None), "k_norm": ((dim,), None),
        "wo": ((heads * dim, d), heads * dim),
        "wq_idx": ((d, j * d_i), d), "wk_idx": ((d, d_i), d),
        "k_idx_gain": ((d_i,), None), "k_idx_bias": ((d_i,), None),
        "w_idx": ((d, j), d), "ffn_norm": ((d,), None),
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


def weights(cfg, seed: int) -> dict:
    """The tree the reference reads; ``program_tree`` turns it into the
    list the program's engine takes."""
    key = jax.random.fold_in(seed_key(seed), 11)
    counter = iter(range(1 << 20))
    assumed = cfg["assumed"]

    def draw(name, spec):
        shape, fan_in = spec
        at = jax.random.fold_in(key, next(counter))
        if name == "k_idx_bias":
            return _normal(at, shape, float(assumed["k_idx_bias_std"]), jnp.float32)
        if fan_in is None:  # a norm gain
            gain = assumed["q_norm_gain"] if name == "q_norm" else 1.0
            return jnp.full(shape, float(gain), jnp.float32)
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
    """Every matrix held here (norm gains and biases not counted, as in
    the configuration's ``parameters_held``)."""
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
