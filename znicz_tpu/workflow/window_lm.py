"""A decoder that mixes WINDOW layers with rotary positions and GLOBAL
layers with no positional signal at all, grouped-query attention over a
few K/V heads, and many small ReLU-gated experts routed from the layer's
INPUT (SmallThinker lineage), served through the paged engine with every
expert held here.

What differs from :mod:`znicz_tpu.workflow.latent_lm`'s block: the cache
holds a ``[v, k]`` row of ``n_kv_heads`` heads a token
(:func:`~znicz_tpu.ops.attention.paged_gqa_attention`); a window layer
turns queries and keys by plain rotary frequencies over the whole head and
attends the last ``window`` keys only, a global layer turns nothing and
attends every key; the router scores the NORMALISED layer input, before
attention, with a softmax over the chosen logits; an expert is ``(relu(h
gate) * h up) down``; there is no shared expert and no dense layer.  The
numerics are the same: weights and cache in one dtype (bfloat16 in
serving), float32 sums, a float32 residual stream.

:class:`WindowGQAMoEModel` is the model KIND the engine is handed, a
:class:`~znicz_tpu.workflow.paged_tower.PagedTower`.  It declares two
:class:`~znicz_tpu.workflow.generate.CacheKind` s, so the engine keeps
blocks, free list and tables for each: the global kind's table is plain,
the window kind's a ring, which is what lets the engine give back the
blocks behind the window while the row lives.

Parameter tree: ``[{"embed"}, block_0, ..., block_{L-1}, {"final_norm",
"head"}]``; a block holds ``attn_norm, wq, wk, wv, wo, ffn_norm, router,
experts_gate, experts_up, experts_down``.
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from znicz_tpu.ops import moe as moe_op
from znicz_tpu.ops.attention import (
    gqa_cache_row,
    paged_gqa_attention,
    paged_gqa_rows_attended,
    paged_gqa_rows_read,
)
from znicz_tpu.ops.normalization import rms_norm
from znicz_tpu.ops.rope import apply_rotary, plain_inv_freq
from znicz_tpu.workflow.generate import CacheKind
from znicz_tpu.workflow.paged_tower import (
    GLOBAL,
    WINDOW,
    PagedTower,
    _dot,
    _rows_a_layer,
)


@dataclasses.dataclass(frozen=True)
class WindowGQAMoEModel(PagedTower):
    """The sizes the parameters do not carry, and which layers are of
    which kind."""

    n_heads: int
    n_kv_heads: int
    head_dim: int
    top_k: int
    window: int  # keys a window layer attends, the query's own among them
    windowed: Tuple[bool, ...]  # a layer: window + rotary, or global + none
    max_positions: int
    norm_topk_prob: bool = True
    rms_eps: float = 1e-6
    rope_theta: float = 10000.0

    @classmethod
    def from_config(cls, cfg: dict, *, max_positions: int):
        """From a published ``config.json`` of the family (``model_name``
        ``smallthinker_*``): layer ``l`` is a window layer where
        ``sliding_window_layout[l]`` is 1, and must then be a rotary layer
        by ``rope_layout`` too; the layouts' first ``num_hidden_layers``
        entries count."""
        n = cfg["num_hidden_layers"]
        windowed = tuple(bool(v) for v in cfg["sliding_window_layout"][:n])
        if windowed != tuple(bool(v) for v in cfg["rope_layout"][:n]):
            raise ValueError(
                "rope_layout and sliding_window_layout differ: only window "
                "layers with rotary positions and global layers without "
                "any are implemented"
            )
        if cfg.get("rope_scaling"):
            raise ValueError("rope_scaling: only plain rotary is implemented")
        if not cfg["moe_primary_router_apply_softmax"]:
            raise ValueError("only a softmax-scored router is implemented")
        return cls(
            n_heads=cfg["num_attention_heads"],
            n_kv_heads=cfg["num_key_value_heads"],
            head_dim=cfg["head_dim"],
            top_k=cfg["moe_num_active_primary_experts"],
            window=cfg["sliding_window_size"], windowed=windowed,
            max_positions=int(max_positions),
            norm_topk_prob=bool(cfg["norm_topk_prob"]),
            rms_eps=float(cfg["rms_norm_eps"]),
            rope_theta=float(cfg["rope_theta"]),
        )

    # -- the cache ----------------------------------------------------------

    @property
    def cache_kinds(self) -> Tuple[CacheKind, ...]:
        return (CacheKind(GLOBAL), CacheKind(WINDOW, self.window))

    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        """The cache kind of each layer's pool, in the tower's order."""
        return tuple(WINDOW if w else GLOBAL for w in self.windowed)

    @staticmethod
    def routed_layers(params) -> int:
        return len(params) - 2

    def _pool_rows(self, block, kind):
        """``[v, k]`` rows of ``n_kv_heads`` heads (``"kv"``)."""
        return {"kv": 2 * self.n_kv_heads * self.head_dim}, block["wk"].dtype

    # -- the tower ----------------------------------------------------------

    def _block_step(self, block, kind, x, pool, write, table, q_pos,
                    row_mask, *, block_size, lengths, decode):
        """One block: rotary and the window in a WINDOW layer only."""
        b, tq, d = x.shape
        eps, windowed = self.rms_eps, kind == WINDOW
        a = rms_norm(x, block["attn_norm"], eps=eps)
        # routed from the layer's input, before attention
        chosen, weight = moe_op.route_softmax_topk(
            a.reshape(b * tq, d).astype(block["router"].dtype),
            block["router"], top_k=self.top_k, normalize=self.norm_topk_prob,
        )
        q = _dot(a, block["wq"]).reshape(b, tq, self.n_heads, self.head_dim)
        k = _dot(a, block["wk"]).reshape(b, tq, self.n_kv_heads, self.head_dim)
        v = _dot(a, block["wv"]).reshape(b, tq, self.n_kv_heads, self.head_dim)
        if windowed:
            inv_freq = plain_inv_freq(self.head_dim, self.rope_theta)
            q = apply_rotary(q, q_pos, inv_freq)
            k = apply_rotary(k, q_pos, inv_freq)
        kv = write(pool["kv"], gqa_cache_row(k, v).astype(pool["kv"].dtype))
        o = paged_gqa_attention(
            q, kv, table, q_pos, block_size=block_size,
            n_kv_heads=self.n_kv_heads, lengths=lengths,
            window=self.window if windowed else None,
        )
        x = x + _dot(o, block["wo"])
        h = rms_norm(x, block["ffn_norm"], eps=eps).reshape(b * tq, d)
        with jax.named_scope("moe_dispatch"):
            y, pairs = moe_op.held_experts_apply(
                h.astype(block["experts_gate"].dtype), chosen, weight,
                block["experts_gate"], block["experts_up"],
                block["experts_down"], first_expert=0,
                row_mask=None if row_mask is None else row_mask.reshape(-1),
                activation=jax.nn.relu,
            )
        return x + y.reshape(b, tq, d), {"kv": kv}, pairs, None

    def _decode_reads(self, tables, lengths, *, block_size):
        """``cached_rows_by_kind``: the cached rows ONE layer of each kind
        FETCHED in this step (:func:`~znicz_tpu.ops.attention.paged_gqa_rows
        _read`: blocks that several live rows of a global layer share count
        once a tile of rows), ``cached_rows``, their mean over the tower's
        layers, and ``attended_rows_by_kind``: the rows the layer's queries
        met, a row counted for each query (:func:`~znicz_tpu.ops.attention
        .paged_gqa_rows_attended`)."""
        by_kind, attended = (
            {
                kind.name: count(
                    tables[kind.name], lengths, block_size=block_size,
                    window=kind.window,
                )
                for kind in self.cache_kinds
            }
            for count in (paged_gqa_rows_read, paged_gqa_rows_attended)
        )
        return dict(
            cached_rows_by_kind=by_kind, attended_rows_by_kind=attended,
            cached_rows=_rows_a_layer(by_kind, self.layer_kinds),
        )


def init_params(
    model: WindowGQAMoEModel, *, d_model: int, vocab: int, d_ff_expert: int,
    n_experts: int, seed: int = 0, dtype=jnp.float32,
):
    """Seeded gaussian parameters (std ``fan_in ** -0.5``, norm gains 1)
    in the tree the engine takes; for tests and examples — a deployment
    loads its own."""
    rng = np.random.default_rng(seed)
    h, g, hd = model.n_heads, model.n_kv_heads, model.head_dim

    def normal(*shape, fan_in=None):
        fan_in = fan_in or shape[-2]
        return jnp.asarray(rng.standard_normal(shape) * fan_in ** -0.5, dtype)

    def ones(n):
        return jnp.ones((n,), jnp.float32)

    blocks = [
        {
            "attn_norm": ones(d_model), "wq": normal(d_model, h * hd),
            "wk": normal(d_model, g * hd), "wv": normal(d_model, g * hd),
            "wo": normal(h * hd, d_model), "ffn_norm": ones(d_model),
            "router": normal(d_model, n_experts),
            "experts_gate": normal(n_experts, d_model, d_ff_expert),
            "experts_up": normal(n_experts, d_model, d_ff_expert),
            "experts_down": normal(n_experts, d_ff_expert, d_model),
        }
        for _ in model.windowed
    ]
    return (
        [{"embed": normal(vocab, d_model, fan_in=d_model)}] + blocks
        + [{"final_norm": ones(d_model), "head": normal(d_model, vocab)}]
    )
