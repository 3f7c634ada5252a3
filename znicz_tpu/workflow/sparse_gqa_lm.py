"""A decoder whose EVERY layer runs grouped-query attention over a few
keys a query, chosen by a learned indexer, with rotary positions over the
whole head, per-head RMS norms on queries and keys, and many small
SiLU-gated experts behind a softmax router that reads the post-attention
norm, all held here (Keye-VL-2.0's language model), served through the
paged engine as one stage of a pipeline: the layers it is handed, the
embedding ahead of them and the head behind.

A :class:`~znicz_tpu.workflow.paged_tower.PagedTower` built from the towers
beside it.  From :mod:`znicz_tpu.workflow.window_lm`: the ``[v, k]`` row of ``n_kv_heads`` heads a token, the
softmax router over every expert, all of them held.  From
:mod:`znicz_tpu.workflow.sparse_latent_lm`: the indexer
(:func:`~znicz_tpu.ops.attention.paged_index_scores`), the exact
selection (:func:`~znicz_tpu.ops.attention.select_top_keys`), a second
pool of the indexer's keys block for block beside the cached rows.  What
is new:

* the selection runs over ``[v, k]`` rows, in every layer: a prefill chunk
  walks the table under the mask with the products grouped a K/V head, a
  decode step FETCHES THE KEPT ROWS and nothing else
  (:func:`~znicz_tpu.ops.attention.kept_gqa_attention`), so what a step
  reads of the cache does not grow with the row's length;
* the indexer's queries come from the normalised layer input (there is no
  query latent), and its one key head is as wide as half a K/V head;
* the tower declares ONE :class:`~znicz_tpu.workflow.generate.CacheKind`
  that keeps its blocks for the row's life, with two arrays a layer in
  it (``"kv"`` and ``"idx"``, one block id for both), so the engine
  serves the prefix cache for it: a shared block carries the indexer's
  keys beside K/V, and a copy-on-write split copies both
  (:func:`~znicz_tpu.workflow.generate.copy_paged_block`).

Numerics are the other towers': weights and cache in one dtype (bfloat16
in serving), float32 sums, norms, rotary, softmax, ReLU and residual
stream.

Parameter tree: ``[{"embed"}, block_0, ..., block_{L-1}, {"final_norm",
"head"}]``; a block holds ``attn_norm, wq, wk, wv, q_norm, k_norm, wo,
wq_idx, wk_idx, k_idx_gain, k_idx_bias, w_idx, ffn_norm, router,
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
    kept_rows_fetched,
    paged_selected_gqa_attention,
)
from znicz_tpu.ops.normalization import layer_norm, rms_norm
from znicz_tpu.ops.rope import apply_rotary, plain_inv_freq
from znicz_tpu.workflow.generate import CacheKind
from znicz_tpu.workflow.paged_tower import GLOBAL, PagedTower, _dot, _tiles


@dataclasses.dataclass(frozen=True)
class SparseGQAMoEModel(PagedTower):
    """The sizes the parameters do not carry."""

    n_layers: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    index_n_heads: int
    index_head_dim: int
    index_topk: int
    top_k: int
    max_positions: int
    norm_topk_prob: bool = True
    rms_eps: float = 1e-6
    rope_theta: float = 1e7

    @classmethod
    def from_config(cls, cfg: dict, *, max_positions: int):
        """From a published ``config.json`` of the family (``model_type``
        ``KeyeVL2``): the language model alone, every position a token
        (the three ``mrope_section`` streams are then equal, which is
        plain rotary over the whole head)."""
        rope = cfg.get("rope_scaling") or {}
        if rope.get("rope_type", rope.get("type", "default")) != "default":
            raise ValueError(f"rope_scaling {rope}: only plain rotary is implemented")
        if cfg.get("decoder_sparse_step", 1) != 1 or cfg.get("mlp_only_layers"):
            raise ValueError("only a tower whose every layer is routed is implemented")
        if cfg.get("use_sliding_window") or cfg.get("attention_bias"):
            raise ValueError("sliding windows and attention biases are not implemented")
        sa = cfg["sa_config"]
        if sa["indexer_num_kv_heads"] != 1:
            raise ValueError("only an indexer with ONE key head is implemented")
        return cls(
            n_layers=cfg["num_hidden_layers"],
            n_heads=cfg["num_attention_heads"],
            n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
            index_n_heads=sa["indexer_num_heads"],
            index_head_dim=sa["indexer_head_dim"], index_topk=sa["topk"],
            top_k=cfg["num_experts_per_tok"], max_positions=int(max_positions),
            norm_topk_prob=bool(cfg["norm_topk_prob"]),
            rms_eps=float(cfg["rms_norm_eps"]),
            rope_theta=float(cfg["rope_theta"]),
        )

    # -- the cache ----------------------------------------------------------

    @property
    def cache_kinds(self) -> Tuple[CacheKind, ...]:
        return (CacheKind(GLOBAL),)

    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        return (GLOBAL,) * self.n_layers

    @property
    def index_row_width(self) -> int:
        """Lanes of a cached indexer key: ``index_head_dim`` rounded up to
        a whole 128-lane tile with zeros (the layout rule of
        :attr:`LatentMoEModel.row_width`; the queries are padded alike, and
        the zeros add exactly)."""
        return _tiles(self.index_head_dim)

    @staticmethod
    def routed_layers(params) -> int:
        return len(params) - 2

    def _pool_rows(self, block, kind):
        """``[v, k]`` rows of ``n_kv_heads`` heads (``"kv"``) and the
        indexer's keys beside them (``"idx"``, :attr:`index_row_width`
        lanes)."""
        lanes = {
            "kv": 2 * self.n_kv_heads * self.head_dim,
            "idx": self.index_row_width,
        }
        return lanes, block["wk"].dtype

    # -- the tower ----------------------------------------------------------

    def _block_step(self, block, kind, x, pool, write, table, q_pos,
                    row_mask, *, block_size, lengths, decode):
        """One block: every layer selects, every layer routes."""
        b, tq, d = x.shape
        eps, hd = self.rms_eps, self.head_dim
        u = rms_norm(x, block["attn_norm"], eps=eps)
        inv_freq = plain_inv_freq(hd, self.rope_theta)
        q = apply_rotary(
            rms_norm(
                _dot(u, block["wq"]).reshape(b, tq, self.n_heads, hd),
                block["q_norm"], eps=eps,
            ),
            q_pos, inv_freq,
        )
        k = apply_rotary(
            rms_norm(
                _dot(u, block["wk"]).reshape(b, tq, self.n_kv_heads, hd),
                block["k_norm"], eps=eps,
            ),
            q_pos, inv_freq,
        )
        v = _dot(u, block["wv"]).reshape(b, tq, self.n_kv_heads, hd)
        kv = write(pool["kv"], gqa_cache_row(k, v).astype(pool["kv"].dtype))
        # the indexer: its own rotary over its whole, narrower head
        idx_freq = plain_inv_freq(self.index_head_dim, self.rope_theta)
        tail = pool["idx"].shape[-1] - self.index_head_dim

        def padded(a):
            return jnp.pad(a, ((0, 0),) * (a.ndim - 1) + ((0, tail),))

        k_idx = apply_rotary(
            layer_norm(
                _dot(u, block["wk_idx"]), block["k_idx_gain"],
                block["k_idx_bias"], eps=eps,
            ),
            q_pos, idx_freq,
        )
        idx = write(pool["idx"], padded(k_idx).astype(pool["idx"].dtype))
        q_idx = apply_rotary(
            _dot(u, block["wq_idx"]).reshape(
                b, tq, self.index_n_heads, self.index_head_dim
            ),
            q_pos, idx_freq,
        )
        w_idx = _dot(u, block["w_idx"]) * (
            self.index_n_heads ** -0.5 * self.index_head_dim ** -0.5
        )
        o, *selection = paged_selected_gqa_attention(
            q, padded(q_idx), w_idx, kv, idx, table, q_pos,
            block_size=block_size, n_kv_heads=self.n_kv_heads,
            top_k=self.index_topk, lengths=lengths,
        )
        x = x + _dot(o, block["wo"])
        h = rms_norm(x, block["ffn_norm"], eps=eps).reshape(b * tq, d)
        h = h.astype(block["router"].dtype)
        with jax.named_scope("moe_dispatch"):
            chosen, weight = moe_op.route_softmax_topk(
                h, block["router"], top_k=self.top_k,
                normalize=self.norm_topk_prob,
            )
            y, pairs = moe_op.held_experts_apply(
                h, chosen, weight, block["experts_gate"], block["experts_up"],
                block["experts_down"], first_expert=0,
                row_mask=None if row_mask is None else row_mask.reshape(-1),
            )
        pool = {"kv": kv, "idx": idx}
        return x + y.reshape(b, tq, d), pool, pairs, selection

    def _decode_reads(self, tables, lengths, *, block_size):
        """``cached_rows``: the ``[v, k]`` rows ONE layer FETCHED
        (:func:`~znicz_tpu.ops.attention.kept_rows_fetched`: at most
        ``index_topk`` a live row, whatever its length); what the indexer
        scored is ``sparse_scored``."""
        fetched = kept_rows_fetched(lengths, self.index_topk)
        return dict(cached_rows=fetched, cached_rows_by_kind={GLOBAL: fetched})


def init_params(
    model: SparseGQAMoEModel, *, d_model: int, vocab: int, d_ff_expert: int,
    n_experts: int, seed: int = 0, dtype=jnp.float32,
):
    """Seeded gaussian parameters (std ``fan_in ** -0.5``, norm gains 1,
    the indexer's key bias a small gaussian) in the tree the engine takes;
    for tests and examples — a deployment loads its own."""
    rng = np.random.default_rng(seed)
    h, g, hd = model.n_heads, model.n_kv_heads, model.head_dim
    j, di = model.index_n_heads, model.index_head_dim

    def normal(*shape, fan_in=None):
        fan_in = fan_in or shape[-2]
        return jnp.asarray(rng.standard_normal(shape) * fan_in ** -0.5, dtype)

    def ones(n):
        return jnp.ones((n,), jnp.float32)

    blocks = [
        {
            "attn_norm": ones(d_model), "wq": normal(d_model, h * hd),
            "wk": normal(d_model, g * hd), "wv": normal(d_model, g * hd),
            "q_norm": ones(hd), "k_norm": ones(hd),
            "wo": normal(h * hd, d_model),
            "wq_idx": normal(d_model, j * di), "wk_idx": normal(d_model, di),
            "k_idx_gain": ones(di),
            "k_idx_bias": jnp.asarray(0.1 * rng.standard_normal(di), jnp.float32),
            "w_idx": normal(d_model, j), "ffn_norm": ones(d_model),
            "router": normal(d_model, n_experts),
            "experts_gate": normal(n_experts, d_model, d_ff_expert),
            "experts_up": normal(n_experts, d_model, d_ff_expert),
            "experts_down": normal(n_experts, d_ff_expert, d_model),
        }
        for _ in range(model.n_layers)
    ]
    return (
        [{"embed": normal(vocab, d_model, fan_in=d_model)}] + blocks
        + [{"final_norm": ones(d_model), "head": normal(d_model, vocab)}]
    )
