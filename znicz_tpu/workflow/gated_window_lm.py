"""A window tower whose layers differ in QUERY HEADS by kind, turn both
kinds by a rotary of their own, gate attention's output a head, lead with a
dense layer and route many small experts beside a shared one from the
post-attention norm (Laguna lineage), served through the paged engine with
every expert held here.

Built on :class:`~znicz_tpu.workflow.window_lm.WindowGQAMoEModel`: the
cache (``[v, k]`` rows of ``n_kv_heads`` heads a token, a global kind and a
window kind whose table is a ring), the pools and what a decode step counts
are that tower's, unchanged.  What this one brings is the block:

- a WINDOW layer has ``n_heads`` query heads, a GLOBAL layer
  ``global_heads``, over the same K/V heads (``wq``, ``wo`` and the gate
  differ in shape by kind);
- a window layer turns queries and keys over the whole head by plain
  frequencies; a global layer turns the first ``rotary_dim`` values of each
  head by YaRN-scaled frequencies and multiplies the turned values' cosines
  and sines by ``attention_factor`` (so the scale lands on queries and keys
  both, on the turned values only), the rest of the head as it is;
- ``g = sigmoid(u wg)``, one value a head of the NORMALISED layer input,
  multiplies attention's output before ``wo``;
- a block with no ``router`` is a dense SiLU-gated feed-forward; the others
  score every expert with a sigmoid of the post-attention norm, choose
  ``top_k`` by score plus a bias an expert, weigh them ``scale * s / sum
  (s)`` and add a shared expert's output;
- a prefill chunk attends in the grouped form (``paged_gqa_attention
  (grouped_prefill=True)``): ``Tq * H`` is 8,192 query rows here.

Parameter tree: ``[{"embed"}, block_0, ..., block_{L-1}, {"final_norm",
"head"}]``; a block holds ``attn_norm, wq, wk, wv, wg, wo, ffn_norm`` and
either ``w_gate, w_up, w_down`` or ``router, router_bias, experts_gate,
experts_up, experts_down, shared_gate, shared_up, shared_down``.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from znicz_tpu.ops import moe as moe_op
from znicz_tpu.ops.attention import gqa_cache_row, paged_gqa_attention
from znicz_tpu.ops.normalization import rms_norm
from znicz_tpu.ops.rope import apply_rotary, plain_inv_freq, yarn_inv_freq
from znicz_tpu.workflow.paged_tower import WINDOW, _dot, _gated
from znicz_tpu.workflow.window_lm import WindowGQAMoEModel

FULL, SLIDING = "full_attention", "sliding_attention"


@dataclasses.dataclass(frozen=True)
class GatedWindowGQAMoEModel(WindowGQAMoEModel):
    """:class:`WindowGQAMoEModel`'s sizes (``n_heads`` the WINDOW layers',
    ``rope_theta`` theirs too) and what the block above adds."""

    global_heads: int = 0
    routed_scaling_factor: float = 1.0
    global_rope_theta: float = 10000.0
    global_rotary_dim: int = 0  # leading values of a head a global layer turns
    rope_factor: float = 1.0
    rope_original_max: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    attention_factor: float = 1.0

    @classmethod
    def from_config(cls, cfg: dict, *, max_positions: int):
        """From a published ``config.json`` of the family (``model_type``
        ``laguna``); the per-layer lists' first ``num_hidden_layers``
        entries count."""
        n = cfg["num_hidden_layers"]
        kinds = tuple(cfg["layer_types"][:n])
        if set(kinds) - {FULL, SLIDING}:
            raise ValueError(f"layer_types {sorted(set(kinds))}: only "
                             f"{FULL} and {SLIDING} are implemented")
        heads = {
            kind: {h for h, k in zip(cfg["num_attention_heads_per_layer"], kinds)
                   if k == kind}
            for kind in (FULL, SLIDING)
        }
        if any(len(v) != 1 for v in heads.values()):
            raise ValueError(
                f"num_attention_heads_per_layer {heads}: one head count a "
                "layer type, and layers of both types, are implemented"
            )
        if cfg["gating"] not in (True, "per-head"):
            raise ValueError(f"gating {cfg['gating']!r}: only a gate a head")
        for key in ("attention_bias", "tie_word_embeddings",
                    "moe_apply_router_weight_on_input"):
            if cfg.get(key, False):
                raise ValueError(f"{key} true: not implemented")
        full = cfg["rope_parameters"][FULL]
        sliding = cfg["rope_parameters"][SLIDING]
        if (full["rope_type"], sliding["rope_type"]) != ("yarn", "default"):
            raise ValueError(
                "rope_parameters: only yarn on the full layers beside the "
                "default on the sliding ones is implemented"
            )
        if sliding.get("partial_rotary_factor", 1) != 1:
            raise ValueError("a sliding layer turns the whole head")
        dense = tuple(t == "dense" for t in cfg["mlp_layer_types"][:n])
        if dense != (True,) + (False,) * (n - 1):
            raise ValueError(
                f"mlp_layer_types {cfg['mlp_layer_types'][:n]}: one leading "
                "dense layer, then sparse ones, is implemented"
            )
        return cls(
            n_heads=heads[SLIDING].pop(), global_heads=heads[FULL].pop(),
            n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
            top_k=cfg["num_experts_per_tok"], window=cfg["sliding_window"],
            windowed=tuple(k == SLIDING for k in kinds),
            max_positions=int(max_positions),
            norm_topk_prob=bool(cfg.get("norm_topk_prob", True)),
            rms_eps=float(cfg["rms_norm_eps"]),
            rope_theta=float(sliding["rope_theta"]),
            routed_scaling_factor=float(cfg["moe_routed_scaling_factor"]),
            global_rope_theta=float(full["rope_theta"]),
            global_rotary_dim=int(
                cfg["head_dim"] * full.get("partial_rotary_factor", 1)
            ),
            rope_factor=float(full["factor"]),
            rope_original_max=int(full["original_max_position_embeddings"]),
            rope_beta_fast=float(full["beta_fast"]),
            rope_beta_slow=float(full["beta_slow"]),
            attention_factor=float(full["attention_factor"]),
        )

    @staticmethod
    def routed_layers(params) -> int:
        return sum(1 for block in params[1:-1] if "router" in block)

    def _turn(self, a, q_pos, windowed):
        """Rotary positions on ``a`` [B, Tq, heads, D], by the layer's
        kind."""
        if windowed:
            return apply_rotary(
                a, q_pos, plain_inv_freq(self.head_dim, self.rope_theta)
            )
        r = self.global_rotary_dim
        inv_freq = yarn_inv_freq(
            r, self.global_rope_theta, factor=self.rope_factor,
            original_max=self.rope_original_max,
            beta_fast=self.rope_beta_fast, beta_slow=self.rope_beta_slow,
        )
        return jnp.concatenate(
            [
                apply_rotary(a[..., :r], q_pos, inv_freq) * self.attention_factor,
                a[..., r:].astype(jnp.float32),
            ],
            axis=-1,
        )

    def _block_step(self, block, kind, x, pool, write, table, q_pos,
                    row_mask, *, block_size, lengths, decode):
        """One block, a dense or a routed one."""
        b, tq, d = x.shape
        eps, g, hd = self.rms_eps, self.n_kv_heads, self.head_dim
        windowed = kind == WINDOW
        heads = self.n_heads if windowed else self.global_heads
        u = rms_norm(x, block["attn_norm"], eps=eps)
        q = self._turn(
            _dot(u, block["wq"]).reshape(b, tq, heads, hd), q_pos, windowed
        )
        k = self._turn(
            _dot(u, block["wk"]).reshape(b, tq, g, hd), q_pos, windowed
        )
        v = _dot(u, block["wv"]).reshape(b, tq, g, hd)
        kv = write(pool["kv"], gqa_cache_row(k, v).astype(pool["kv"].dtype))
        o = paged_gqa_attention(
            q, kv, table, q_pos, block_size=block_size, n_kv_heads=g,
            lengths=lengths, window=self.window if windowed else None,
            grouped_prefill=True,
        )
        with jax.named_scope("attn_gate"):
            gate = jax.nn.sigmoid(_dot(u, block["wg"]))  # [B, Tq, heads]
            o = (o.reshape(b, tq, heads, hd) * gate[..., None]).reshape(b, tq, -1)
        x = x + _dot(o, block["wo"])
        h = rms_norm(x, block["ffn_norm"], eps=eps).reshape(b * tq, d)
        if "router" not in block:
            with jax.named_scope("ffn_dense"):
                y = _gated(h, block["w_gate"], block["w_up"], block["w_down"])
            return x + y.reshape(b, tq, d), {"kv": kv}, None, None
        h = h.astype(block["router"].dtype)
        with jax.named_scope("moe_dispatch"):
            chosen, weight = moe_op.route_sigmoid_topk(
                h, block["router"], top_k=self.top_k,
                scale=self.routed_scaling_factor,
                normalize=self.norm_topk_prob, bias=block["router_bias"],
            )
            y, pairs = moe_op.held_experts_apply(
                h, chosen, weight, block["experts_gate"], block["experts_up"],
                block["experts_down"], first_expert=0,
                row_mask=None if row_mask is None else row_mask.reshape(-1),
            )
        with jax.named_scope("moe_shared"):
            y = y + _gated(
                h, block["shared_gate"], block["shared_up"], block["shared_down"]
            )
        return x + y.reshape(b, tq, d), {"kv": kv}, pairs, None


def init_params(
    model: GatedWindowGQAMoEModel, *, d_model: int, vocab: int,
    d_ff_dense: int, d_ff_expert: int, n_experts: int, first_dense: int = 1,
    seed: int = 0, dtype=jnp.float32,
):
    """Seeded gaussian parameters (std ``fan_in ** -0.5``, norm gains 1,
    the router's choice bias 0) in the tree the engine takes; for tests and
    examples — a deployment loads its own."""
    rng = np.random.default_rng(seed)
    g, hd = model.n_kv_heads, model.head_dim

    def normal(*shape, fan_in=None):
        fan_in = fan_in or shape[-2]
        return jnp.asarray(rng.standard_normal(shape) * fan_in ** -0.5, dtype)

    def ones(n):
        return jnp.ones((n,), jnp.float32)

    blocks = []
    for layer, windowed in enumerate(model.windowed):
        h = model.n_heads if windowed else model.global_heads
        block = {
            "attn_norm": ones(d_model), "wq": normal(d_model, h * hd),
            "wk": normal(d_model, g * hd), "wv": normal(d_model, g * hd),
            "wg": normal(d_model, h), "wo": normal(h * hd, d_model),
            "ffn_norm": ones(d_model),
        }
        if layer < first_dense:
            block.update(
                w_gate=normal(d_model, d_ff_dense),
                w_up=normal(d_model, d_ff_dense),
                w_down=normal(d_ff_dense, d_model),
            )
        else:
            block.update(
                router=normal(d_model, n_experts),
                router_bias=jnp.zeros((n_experts,), jnp.float32),
                experts_gate=normal(n_experts, d_model, d_ff_expert),
                experts_up=normal(n_experts, d_model, d_ff_expert),
                experts_down=normal(n_experts, d_ff_expert, d_model),
                shared_gate=normal(d_model, d_ff_expert),
                shared_up=normal(d_model, d_ff_expert),
                shared_down=normal(d_ff_expert, d_model),
            )
        blocks.append(block)
    return (
        [{"embed": normal(vocab, d_model, fan_in=d_model)}] + blocks
        + [{"final_norm": ones(d_model), "head": normal(d_model, vocab)}]
    )
