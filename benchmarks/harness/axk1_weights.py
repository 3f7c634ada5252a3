"""Seeded weights of the ``axk1-ep16`` configuration, drawn ON THE DEVICE
leaf by leaf in the type they are served in (bfloat16; norm gains
float32): 4.2 billion values are not drawn on the host, and one leaf at a
time keeps the float32 draw of the largest (a layer's 12 x 7168 x 2048
expert matrices, 0.7 GB) the only temporary.  The driver hands the SAME
arrays to the program and to the plain reference.

``assumed.weights``: gaussian, std ``fan_in ** -0.5`` (the embedding:
``hidden_size ** -0.5``), every norm gain 1."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from harness.weights import seed_key


def leaf_shapes(cfg) -> dict:
    """``{"embed", "final_norm", "head", "blocks": [{leaf: (shape,
    fan_in or None)}]}``: every leaf of the share this chip holds.  A
    ``None`` fan-in marks a norm gain."""
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    d_q, d_c = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    d_n, d_r, d_v = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    f_dense, f_exp = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    held = cfg["n_routed_experts"]
    published = cfg["deployment"]["n_routed_experts_published"]
    blocks = []
    for layer in range(cfg["num_hidden_layers"]):
        block = {
            "attn_norm": ((d,), None), "wq_a": ((d, d_q), d),
            "q_norm": ((d_q,), None),
            "wq_b_nope": ((d_q, heads * d_n), d_q),
            "wq_b_rope": ((d_q, heads * d_r), d_q),
            "wkv_a": ((d, d_c + d_r), d), "kv_norm": ((d_c,), None),
            "wk_b": ((d_c, heads * d_n), d_c), "wv_b": ((d_c, heads * d_v), d_c),
            "wo": ((heads * d_v, d), heads * d_v), "ffn_norm": ((d,), None),
        }
        if layer < cfg["first_k_dense_replace"]:
            block.update(
                w_gate=((d, f_dense), d), w_up=((d, f_dense), d),
                w_down=((f_dense, d), f_dense),
            )
        else:
            shared = f_exp * cfg["n_shared_experts"]
            block.update(
                router=((d, published), d),
                experts_gate=((held, d, f_exp), d),
                experts_up=((held, d, f_exp), d),
                experts_down=((held, f_exp, d), f_exp),
                shared_gate=((d, shared), d), shared_up=((d, shared), d),
                shared_down=((shared, d), shared),
            )
        blocks.append(block)
    vocab = cfg["vocab_size"]
    return {
        "embed": ((vocab, d), d), "final_norm": ((d,), None),
        "head": ((d, vocab), d), "blocks": blocks,
    }


@functools.partial(jax.jit, static_argnames=("shape", "std"))
def _normal(key, shape, std):
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(jnp.bfloat16)


def weights(cfg, seed: int) -> dict:
    """The tree the reference reads; ``program_tree`` turns it into the
    list the program's engine takes."""
    key = jax.random.fold_in(seed_key(seed), 3)
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
    shapes = leaf_shapes(cfg)
    leaves = [shapes["embed"], shapes["final_norm"], shapes["head"]] + [
        spec for block in shapes["blocks"] for spec in block.values()
    ]
    total = 0
    for shape, _ in leaves:
        n = 1
        for dim in shape:
            n *= dim
        total += n
    return total
