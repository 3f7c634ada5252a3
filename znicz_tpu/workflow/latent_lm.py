"""A decoder with multi-head LATENT attention and many small routed
experts beside a shared one (DeepSeek-V2/V3 lineage), served through the
paged engine as ONE chip's share of an expert-parallel deployment.

What differs from :mod:`znicz_tpu.workflow.transformer`'s block: RMS
norms and no biases; rotary positions (YaRN-scaled) on a narrow part of
each head instead of a learned table; queries through a low-rank
bottleneck; keys and values as ONE normalised latent row a token, which
is what the cache stores; gated (SwiGLU) feed-forwards; a router that
scores every published expert with a sigmoid, of which the experts
``[first_expert, first_expert + held)`` live here; a final norm; a head
over this chip's slice of the vocabulary.  Weights and cache are stored
in one dtype (bfloat16 in serving), every product accumulates in float32
and the residual stream between products stays float32.

:class:`LatentMoEModel` is the model KIND the engine is handed
(``PagedDecodeEngine(params, ..., model=LatentMoEModel(...))``): a frozen,
hashable description, so it is a static argument of the engine's compiled
programs.  The three functions the paged engine needs of a tower are
:class:`~znicz_tpu.workflow.paged_tower.PagedTower`'s; what is here is the
block: a prefill chunk attends by the MATERIALISED form, a decode step by
the ABSORBED one.

Parameter tree: ``[{"embed"}, block_0, ..., block_{L-1}, {"final_norm",
"head"}]``; a block holds ``attn_norm, wq_a, q_norm, wq_b_nope,
wq_b_rope, wkv_a, kv_norm, wk_b, wv_b, wo, ffn_norm`` and either ``w_gate,
w_up, w_down`` (a dense layer) or ``router, experts_gate, experts_up,
experts_down, shared_gate, shared_up, shared_down``.
"""

from __future__ import annotations

import dataclasses
import jax
import jax.numpy as jnp
import numpy as np

from znicz_tpu.ops import moe as moe_op
from znicz_tpu.ops.attention import (
    paged_latent_attention,
    paged_latent_rows_read,
)
from znicz_tpu.ops.normalization import rms_norm
from znicz_tpu.ops.rope import (
    apply_rotary,
    yarn_attention_factor,
    yarn_inv_freq,
)
from znicz_tpu.workflow.paged_tower import PagedTower, _dot, _gated, _tiles


@dataclasses.dataclass(frozen=True)
class LatentMoEModel(PagedTower):
    """The sizes the parameters do not carry, and what of the model
    this chip holds."""

    n_heads: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    top_k: int
    routed_scaling_factor: float
    first_expert: int  # experts [first_expert, first_expert + held) live here
    max_positions: int
    norm_topk_prob: bool = True
    rms_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_factor: float = 1.0
    rope_original_max: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale_all_dim: float = 1.0

    @classmethod
    def from_config(cls, cfg: dict, *, first_expert: int, max_positions: int):
        """From a published ``config.json`` of the family (the keys of
        ``model_type`` ``deepseek_v3`` / ``axk1``)."""
        if cfg.get("topk_method", "none") not in ("none", "greedy"):
            raise ValueError(
                f"topk_method {cfg['topk_method']!r}: only a plain top-k "
                "over all experts is implemented (no group limit, no "
                "score bias)"
            )
        rope = cfg.get("rope_scaling") or {}
        return cls(
            n_heads=cfg["num_attention_heads"],
            kv_lora_rank=cfg["kv_lora_rank"],
            qk_nope_head_dim=cfg["qk_nope_head_dim"],
            qk_rope_head_dim=cfg["qk_rope_head_dim"],
            top_k=cfg["num_experts_per_tok"],
            routed_scaling_factor=float(cfg["routed_scaling_factor"]),
            first_expert=int(first_expert),
            max_positions=int(max_positions),
            norm_topk_prob=bool(cfg["norm_topk_prob"]),
            rms_eps=float(cfg["rms_norm_eps"]),
            rope_theta=float(cfg["rope_theta"]),
            rope_factor=float(rope.get("factor", 1.0)),
            rope_original_max=int(
                rope.get("original_max_position_embeddings", max_positions)
            ),
            rope_beta_fast=float(rope.get("beta_fast", 32.0)),
            rope_beta_slow=float(rope.get("beta_slow", 1.0)),
            rope_mscale_all_dim=float(rope.get("mscale_all_dim", 1.0)),
        )

    # -- derived sizes ------------------------------------------------------

    @property
    def softmax_scale(self) -> float:
        m = yarn_attention_factor(self.rope_factor, self.rope_mscale_all_dim)
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5 * m * m

    @property
    def row_width(self) -> int:
        """Width of a cached row: latent + rotated key, rounded up to
        whole 128-lane tiles with zeros.  On the TPU a 576-wide row takes
        640 lanes in any layout a program computes in; stored at 576 the
        compiler keeps the pool tokens-minor instead, and every call then
        copies every pool in and out of the layout it gathers from (6 x
        0.64 GB of temporaries at the axk1-ep16 sizes: the decode program
        did not fit the chip)."""
        return _tiles(self.kv_lora_rank + self.qk_rope_head_dim)

    def _inv_freq(self):
        return yarn_inv_freq(
            self.qk_rope_head_dim, self.rope_theta, factor=self.rope_factor,
            original_max=self.rope_original_max,
            beta_fast=self.rope_beta_fast, beta_slow=self.rope_beta_slow,
        )

    @staticmethod
    def routed_layers(params) -> int:
        return sum(1 for block in params[1:-1] if "router" in block)

    # -- the cache ----------------------------------------------------------

    def _pool_rows(self, block, kind):
        """Latent rows of :attr:`row_width` lanes (``"kv"``)."""
        return {"kv": self.row_width}, block["wkv_a"].dtype

    # -- the tower ----------------------------------------------------------

    def _block_step(
        self, block, kind, x, pool, write, tables, q_pos, row_mask, *,
        block_size, lengths, decode,
    ):
        """One block: the attention ABSORBED in a decode step, MATERIALISED
        in a prefill chunk."""
        b, tq, d = x.shape
        eps, dc = self.rms_eps, self.kv_lora_rank
        inv_freq = self._inv_freq()
        h = rms_norm(x, block["attn_norm"], eps=eps)
        c_q = rms_norm(_dot(h, block["wq_a"]), block["q_norm"], eps=eps)
        q_nope = _dot(c_q, block["wq_b_nope"]).reshape(b, tq, self.n_heads, -1)
        q_rope = apply_rotary(
            _dot(c_q, block["wq_b_rope"]).reshape(b, tq, self.n_heads, -1),
            q_pos, inv_freq,
        )
        kv = _dot(h, block["wkv_a"])
        row = jnp.concatenate(
            [
                rms_norm(kv[..., :dc], block["kv_norm"], eps=eps),
                apply_rotary(kv[..., dc:], q_pos, inv_freq),
                jnp.zeros((b, tq, self.row_width - kv.shape[-1]), jnp.float32),
            ],
            axis=-1,
        )
        kv_pool = write(pool["kv"], row.astype(pool["kv"].dtype))
        o = paged_latent_attention(
            q_nope, q_rope, kv_pool, tables, q_pos, block["wk_b"],
            block["wv_b"], block_size=block_size, scale=self.softmax_scale,
            absorbed=decode, lengths=lengths,
        )
        x = x + _dot(o, block["wo"])
        h = rms_norm(x, block["ffn_norm"], eps=eps).reshape(b * tq, d)
        if "router" not in block:
            y, pairs = _gated(h, block["w_gate"], block["w_up"], block["w_down"]), None
        else:
            h = h.astype(block["router"].dtype)
            with jax.named_scope("moe_dispatch"):
                chosen, weight = moe_op.route_sigmoid_topk(
                    h, block["router"], top_k=self.top_k,
                    scale=self.routed_scaling_factor,
                    normalize=self.norm_topk_prob,
                )
                y, pairs = moe_op.held_experts_apply(
                    h, chosen, weight, block["experts_gate"],
                    block["experts_up"], block["experts_down"],
                    first_expert=self.first_expert,
                    row_mask=None if row_mask is None else row_mask.reshape(-1),
                )
            y = y + _gated(
                h, block["shared_gate"], block["shared_up"], block["shared_down"]
            )
        return x + y.reshape(b, tq, d), {"kv": kv_pool}, pairs, None

    def _decode_reads(self, tables, lengths, *, block_size):
        """``cached_rows``: the cached rows a layer's attention read in
        this step, as the form that ran counts them
        (:func:`~znicz_tpu.ops.attention.paged_latent_rows_read`)."""
        return {
            "cached_rows": paged_latent_rows_read(
                tables, lengths, block_size=block_size
            )
        }


def init_params(
    model: LatentMoEModel, *, d_model: int, n_layers: int, vocab: int,
    q_lora_rank: int, v_head_dim: int, d_ff_dense: int, d_ff_expert: int,
    n_routed_experts: int, held_experts: int, first_dense: int = 1,
    seed: int = 0, dtype=jnp.float32,
):
    """Seeded gaussian parameters (std ``fan_in ** -0.5``, norm gains 1)
    in the tree the engine takes; for tests and examples — a deployment
    loads its own."""
    rng = np.random.default_rng(seed)
    h, dn, dr = model.n_heads, model.qk_nope_head_dim, model.qk_rope_head_dim
    dc = model.kv_lora_rank

    def normal(*shape, fan_in=None):
        fan_in = fan_in or shape[-2]
        return jnp.asarray(
            rng.standard_normal(shape) * fan_in ** -0.5, dtype
        )

    def ones(n):
        return jnp.ones((n,), jnp.float32)

    blocks = []
    for layer in range(n_layers):
        block = {
            "attn_norm": ones(d_model), "wq_a": normal(d_model, q_lora_rank),
            "q_norm": ones(q_lora_rank),
            "wq_b_nope": normal(q_lora_rank, h * dn),
            "wq_b_rope": normal(q_lora_rank, h * dr),
            "wkv_a": normal(d_model, dc + dr), "kv_norm": ones(dc),
            "wk_b": normal(dc, h * dn), "wv_b": normal(dc, h * v_head_dim),
            "wo": normal(h * v_head_dim, d_model), "ffn_norm": ones(d_model),
        }
        if layer < first_dense:
            block.update(
                w_gate=normal(d_model, d_ff_dense),
                w_up=normal(d_model, d_ff_dense),
                w_down=normal(d_ff_dense, d_model),
            )
        else:
            block.update(
                router=normal(d_model, n_routed_experts),
                experts_gate=normal(held_experts, d_model, d_ff_expert),
                experts_up=normal(held_experts, d_model, d_ff_expert),
                experts_down=normal(held_experts, d_ff_expert, d_model),
                shared_gate=normal(d_model, d_ff_expert),
                shared_up=normal(d_model, d_ff_expert),
                shared_down=normal(d_ff_expert, d_model),
            )
        blocks.append(block)
    return (
        [{"embed": normal(vocab, d_model, fan_in=d_model)}] + blocks
        + [{"final_norm": ones(d_model), "head": normal(d_model, vocab)}]
    )
