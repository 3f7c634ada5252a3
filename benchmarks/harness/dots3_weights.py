"""Seeded weights of the ``dots3-ep16-l5`` configuration, drawn ON THE
DEVICE leaf by leaf in the type they are served in (bfloat16; norm gains,
the indexer's key bias and the router's score bias float32): 2.6 billion
values are not drawn on the host, and one leaf at a time keeps the float32
draw of the largest (a layer's 16 x 5120 x 1536 expert matrices, 0.5 GB)
the only temporary.  The driver hands the SAME arrays to the program and
to the plain reference.

``assumed.weights``: gaussian, std ``fan_in ** -0.5`` (the embedding:
``hidden_size ** -0.5``), every norm gain 1 — except the matrices that make
QUERIES (``wq_b_nope``, ``wq_b_rope``, ``wq_idx``), whose std is
``assumed.query_gain`` times that.  With plain ``fan_in ** -0.5`` draws
the latents' rescale (``(hidden / rank) ** 0.5`` on both sides of every
score) already spreads a full layer's attention logits by ~6 and a window
layer's by ~4.5, a softmax that one key in a thousand decides; at a gain
of 0.5 they spread by ~3 and ~2.2 and the indexer's scores by ~0.65, so
that WHICH keys are attended decides the logits without one key deciding
them alone.  The router's score bias (``noaux_tc``) is a gaussian of std
``assumed.router_bias_std`` beside sigmoid scores that spread by ~0.2, the
indexer's key bias one of std 0.1.  The head's column of ``assumed.eos_id``
is ZERO (``assumed.eos_column``): its logit is 0 beside a best of ~4, so an
answer ends at its budget and never at a chance end-of-sequence."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from harness.smallthinker_weights import program_tree  # noqa: F401  (the same tree)
from harness.weights import seed_key

QUERY_SIDE = ("wq_b_nope", "wq_b_rope", "wq_idx")
# float32 vectors drawn, not set to 1: leaf name -> the assumed key of its std
DRAWN_VECTORS = {"router_bias": "router_bias_std", "k_idx_bias": "k_idx_bias_std"}


def leaf_shapes(cfg) -> dict:
    """``{"embed", "final_norm", "head", "blocks": [{leaf: (shape, fan_in
    or None)}]}``: every leaf of the share this chip holds.  A ``None``
    fan-in marks a float32 vector (a norm gain or a bias)."""
    d = cfg["hidden_size"]
    f_dense, f_exp = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    held = cfg["n_routed_experts"]
    published = cfg["deployment"]["n_routed_experts_published"]
    blocks = []
    for layer, kind in enumerate(cfg["layer_types"][: cfg["num_hidden_layers"]]):
        p = "" if kind == "full_attention" else "swa_"
        heads, d_q, d_c = (
            cfg[p + "num_attention_heads"], cfg[p + "q_lora_rank"],
            cfg[p + "kv_lora_rank"],
        )
        d_n, d_r, d_v = (
            cfg[p + "qk_nope_head_dim"], cfg[p + "qk_rope_head_dim"],
            cfg[p + "v_head_dim"],
        )
        block = {
            "attn_norm": ((d,), None), "wq_a": ((d, d_q), d),
            "q_norm": ((d_q,), None),
            "wq_b_nope": ((d_q, heads * d_n), d_q),
            "wq_b_rope": ((d_q, heads * d_r), d_q),
            "wkv_a": ((d, d_c + d_r), d), "kv_norm": ((d_c,), None),
            "wk_b": ((d_c, heads * d_n), d_c), "wv_b": ((d_c, heads * d_v), d_c),
            "wg": ((d, heads), d), "wo": ((heads * d_v, d), heads * d_v),
            "ffn_norm": ((d,), None),
        }
        if kind == "full_attention":
            j, d_i = cfg["index_n_heads"], cfg["index_head_dim"]
            block.update(
                wq_idx=((d_q, j * d_i), d_q), wk_idx=((d, d_i), d),
                k_idx_gain=((d_i,), None), k_idx_bias=((d_i,), None),
                w_idx=((d, j), d),
            )
        if layer < cfg["first_k_dense_replace"]:
            block.update(
                w_gate=((d, f_dense), d), w_up=((d, f_dense), d),
                w_down=((f_dense, d), f_dense),
            )
        else:
            shared = f_exp * cfg["n_shared_experts"]
            block.update(
                router=((d, published), d), router_bias=((published,), None),
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


@functools.partial(jax.jit, static_argnames=("shape", "std", "dtype"))
def _normal(key, shape, std, dtype):
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def weights(cfg, seed: int) -> dict:
    """The tree the reference reads; ``program_tree`` (``harness
    /smallthinker_weights``'s) turns it into the list the program's engine
    takes."""
    key = jax.random.fold_in(seed_key(seed), 7)
    counter = iter(range(1 << 20))
    assumed = cfg["assumed"]

    def draw(name, spec):
        shape, fan_in = spec
        at = jax.random.fold_in(key, next(counter))
        if name in DRAWN_VECTORS:
            return _normal(at, shape, float(assumed[DRAWN_VECTORS[name]]), jnp.float32)
        if fan_in is None:
            return jnp.ones(shape, jnp.float32)
        gain = float(assumed["query_gain"]) if name in QUERY_SIDE else 1.0
        return _normal(at, shape, gain * float(fan_in) ** -0.5, jnp.bfloat16)

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
