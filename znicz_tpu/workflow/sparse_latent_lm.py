"""A decoder whose FULL layers run latent attention over a few keys a
query, chosen by a learned indexer, and whose other layers run a second,
wider latent attention behind a short window; head-wise sigmoid gates on
both, and many small sigmoid-routed experts beside a shared one, chosen
with a score bias (dots3-note lineage), served through the paged engine as
ONE chip's share of an expert-parallel deployment.

A :class:`~znicz_tpu.workflow.paged_tower.PagedTower` built from the two
towers beside it.  From :mod:`znicz_tpu.workflow.latent_lm`: the latent row
a token, the low-rank queries, the absorbed products, the routed layer that
is told which experts it holds, the sliced head.  From :mod:`znicz_tpu
.workflow.window_lm`: two :class:`~znicz_tpu.workflow.generate.CacheKind` s,
so the engine keeps blocks, free list and tables for each, the window
kind's table a ring.  What is new:

* the two kinds of layer have their OWN sizes (heads, latent rank, key
  width, rotary base), so the two kinds of cached row differ in width: a
  full layer keeps ``[c, rot(k_r), zeros]`` and, in a second pool of the
  same blocks, the indexer's key; a window layer its own, wider ``[c,
  rot(k_r), zeros]``; each rounded up to whole 128-lane tiles
  (:attr:`SparseLatentMoEModel.row_widths`);
* a full layer scores every cached token with the indexer, keeps the
  ``index_topk`` best and attends those alone
  (:func:`~znicz_tpu.ops.attention.paged_selected_latent_attention`) in
  the prefill chunk and the decode step alike: neither forms K, V or
  scores over the table's width;
* the latents are rescaled after their norms (``(hidden / rank) ** 0.5``),
  every head's output is gated by ``sigmoid(u wg)`` of the normalised
  layer input, and the router's choice adds a bias an expert to the
  scores while the gate values stay the unbiased scores.

Numerics are the other towers': weights and cache in one dtype (bfloat16
in serving), float32 sums, norms, rotary, softmax, sigmoids and residual
stream.

Parameter tree: ``[{"embed"}, block_0, ..., block_{L-1}, {"final_norm",
"head"}]``; a block holds ``attn_norm, wq_a, q_norm, wq_b_nope, wq_b_rope,
wkv_a, kv_norm, wk_b, wv_b, wg, wo, ffn_norm``, a full layer also
``wq_idx, wk_idx, k_idx_gain, k_idx_bias, w_idx``, and either ``w_gate,
w_up, w_down`` (a dense layer) or ``router, router_bias, experts_gate,
experts_up, experts_down, shared_gate, shared_up, shared_down``.
"""

from __future__ import annotations

import dataclasses
from typing import Mapping, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from znicz_tpu.ops import moe as moe_op
from znicz_tpu.ops.attention import (
    paged_gqa_rows_read,
    paged_latent_rows_read,
    paged_selected_latent_attention,
    paged_window_latent_attention,
)
from znicz_tpu.ops.normalization import layer_norm, rms_norm
from znicz_tpu.ops.rope import apply_rotary, plain_inv_freq
from znicz_tpu.workflow.generate import CacheKind
from znicz_tpu.workflow.paged_tower import (
    GLOBAL,
    WINDOW,
    PagedTower,
    _dot,
    _gated,
    _rows_a_layer,
    _tiles,
)


@dataclasses.dataclass(frozen=True)
class LatentSizes:
    """One kind of layer's attention: heads, the cached latent's rank, a
    head's key widths without and with rotary positions, the rotary base,
    and what the latents are multiplied by after their norms."""

    n_heads: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    rope_theta: float
    q_rescale: float = 1.0
    kv_rescale: float = 1.0

    @property
    def softmax_scale(self) -> float:
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5


@dataclasses.dataclass(frozen=True)
class SparseLatentMoEModel(PagedTower):
    """The sizes the parameters do not carry, which layers are of which
    kind, and what of the model this chip holds."""

    full: LatentSizes
    swa: LatentSizes
    full_layers: Tuple[bool, ...]  # a layer: full + indexer, or window
    window: int  # keys a window layer attends, the query's own among them
    index_n_heads: int
    index_head_dim: int
    index_topk: int
    top_k: int
    routed_scaling_factor: float
    first_expert: int  # experts [first_expert, first_expert + held) live here
    max_positions: int
    norm_topk_prob: bool = True
    rms_eps: float = 1e-5

    @classmethod
    def from_config(cls, cfg: dict, *, first_expert: int, max_positions: int):
        """From a published ``config.json`` of the family (``model_type``
        ``dots3_note``): the first ``num_hidden_layers`` entries of
        ``layer_types`` count."""
        if cfg.get("topk_method") != "noaux_tc" or cfg.get("scoring_func") != "sigmoid":
            raise ValueError(
                "only sigmoid scores chosen with a score bias (noaux_tc) "
                f"are implemented; got {cfg.get('scoring_func')!r}, "
                f"{cfg.get('topk_method')!r}"
            )
        if cfg.get("rope_scaling"):
            raise ValueError("rope_scaling: only plain rotary is implemented")
        gates = {cfg.get("attention_gate_type"), cfg.get("swa_attention_gate_type")}
        if gates != {"headwise"}:
            raise ValueError(f"only head-wise output gates are implemented; got {gates}")
        kinds = cfg["layer_types"][: cfg["num_hidden_layers"]]
        if set(kinds) - {"full_attention", "sliding_attention"}:
            raise ValueError(f"layer_types {sorted(set(kinds))}")
        hidden = cfg["hidden_size"]
        rescale = bool(cfg.get("apply_mla_qkv_lora_rescale"))

        def sizes(prefix, q_rank, theta):
            rank = cfg[prefix + "kv_lora_rank"]
            return LatentSizes(
                n_heads=cfg[prefix + "num_attention_heads"], kv_lora_rank=rank,
                qk_nope_head_dim=cfg[prefix + "qk_nope_head_dim"],
                qk_rope_head_dim=cfg[prefix + "qk_rope_head_dim"],
                rope_theta=float(theta),
                q_rescale=(hidden / q_rank) ** 0.5 if rescale else 1.0,
                kv_rescale=(hidden / rank) ** 0.5 if rescale else 1.0,
            )

        return cls(
            full=sizes("", cfg["q_lora_rank"], cfg["rope_theta"]),
            swa=sizes("swa_", cfg["swa_q_lora_rank"], cfg["swa_rope_theta"]),
            full_layers=tuple(k == "full_attention" for k in kinds),
            window=int(cfg["sliding_window_size"]),
            index_n_heads=cfg["index_n_heads"],
            index_head_dim=cfg["index_head_dim"],
            index_topk=cfg["index_topk"],
            top_k=cfg["num_experts_per_tok"],
            routed_scaling_factor=float(cfg["routed_scaling_factor"]),
            first_expert=int(first_expert), max_positions=int(max_positions),
            norm_topk_prob=bool(cfg["norm_topk_prob"]),
            rms_eps=float(cfg["rms_norm_eps"]),
        )

    # -- the cache ----------------------------------------------------------

    @property
    def cache_kinds(self) -> Tuple[CacheKind, ...]:
        return (CacheKind(GLOBAL), CacheKind(WINDOW, self.window))

    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        """The cache kind of each layer's pool, in the tower's order."""
        return tuple(GLOBAL if f else WINDOW for f in self.full_layers)

    @property
    def row_widths(self) -> Mapping[str, int]:
        """Lanes of a cached latent row by kind, ``[c, rot(k_r)]`` rounded
        up to whole 128-lane tiles (the layout rule of
        :attr:`LatentMoEModel.row_width`): the two kinds' differ."""
        return {
            GLOBAL: _tiles(self.full.kv_lora_rank + self.full.qk_rope_head_dim),
            WINDOW: _tiles(self.swa.kv_lora_rank + self.swa.qk_rope_head_dim),
        }

    @staticmethod
    def routed_layers(params) -> int:
        return sum(1 for block in params[1:-1] if "router" in block)

    def _pool_rows(self, block, kind):
        """Latent rows of ``row_widths[kind]`` lanes (``"kv"``) and, in a
        full layer, the indexer's keys beside them (``"idx"``,
        ``index_head_dim`` lanes).

        The indexer's keys do not ride in the latent row's tail lanes:
        the indexer reads 128 lanes of EVERY cached token and attention
        640 lanes of 2,048, and for a gather of a lane-slice the TPU's
        compiler re-lays the whole pool (a 1.6 GB copy a layer in every
        call at dots3-ep16-l5's sizes: AOT compile, PR 36)."""
        lanes = {"kv": self.row_widths[kind]}
        if kind == GLOBAL:
            lanes["idx"] = self.index_head_dim
        return lanes, block["wkv_a"].dtype

    # -- the tower ----------------------------------------------------------

    def _attention(self, block, full, u, pool, write, table, q_pos, *,
                   block_size, lengths):
        """The attention update of one layer from its normalised input
        ``u`` [B, Tq, D]: ``(update [B, Tq, D], pool, scored, selected)``
        (the counts None in a window layer)."""
        b, tq, _ = u.shape
        sizes = self.full if full else self.swa
        eps, dc = self.rms_eps, sizes.kv_lora_rank
        inv_freq = plain_inv_freq(sizes.qk_rope_head_dim, sizes.rope_theta)
        c_q = sizes.q_rescale * rms_norm(
            _dot(u, block["wq_a"]), block["q_norm"], eps=eps
        )
        q_nope = _dot(c_q, block["wq_b_nope"]).reshape(b, tq, sizes.n_heads, -1)
        q_rope = apply_rotary(
            _dot(c_q, block["wq_b_rope"]).reshape(b, tq, sizes.n_heads, -1),
            q_pos, inv_freq,
        )
        kv = _dot(u, block["wkv_a"])
        row = jnp.concatenate(
            [
                sizes.kv_rescale * rms_norm(kv[..., :dc], block["kv_norm"], eps=eps),
                apply_rotary(kv[..., dc:], q_pos, inv_freq),
                jnp.zeros(
                    (b, tq, pool["kv"].shape[-1] - kv.shape[-1]), jnp.float32
                ),
            ],
            axis=-1,
        )
        new_pool = {"kv": write(pool["kv"], row.astype(pool["kv"].dtype))}
        selection = None
        if full:
            turned = sizes.qk_rope_head_dim  # leading values of q_I, k_I

            def turn(a):
                return jnp.concatenate(
                    [apply_rotary(a[..., :turned], q_pos, inv_freq), a[..., turned:]],
                    axis=-1,
                )

            k_idx = turn(layer_norm(
                _dot(u, block["wk_idx"]), block["k_idx_gain"],
                block["k_idx_bias"], eps=eps,
            ))
            new_pool["idx"] = write(pool["idx"], k_idx.astype(pool["idx"].dtype))
            q_idx = turn(_dot(c_q, block["wq_idx"]).reshape(
                b, tq, self.index_n_heads, self.index_head_dim
            ))
            w_idx = _dot(u, block["w_idx"]) * (
                self.index_n_heads ** -0.5 * self.index_head_dim ** -0.5
            )
            o, *selection = paged_selected_latent_attention(
                q_nope, q_rope, q_idx, w_idx, new_pool["kv"], new_pool["idx"],
                table, q_pos, block["wk_b"], block["wv_b"],
                block_size=block_size, scale=sizes.softmax_scale,
                top_k=self.index_topk, lengths=lengths,
            )
        else:
            o = paged_window_latent_attention(
                q_nope, q_rope, new_pool["kv"], table, q_pos, block["wk_b"],
                block["wv_b"], block_size=block_size,
                scale=sizes.softmax_scale, window=self.window, lengths=lengths,
            )
        gate = jax.nn.sigmoid(_dot(u, block["wg"]))  # [B, Tq, H], one a head
        o = (o.reshape(b, tq, sizes.n_heads, -1) * gate[..., None]).reshape(b, tq, -1)
        return _dot(o, block["wo"]), new_pool, selection

    def _feed_forward(self, block, h, row_mask):
        """The feed-forward of one layer on normalised rows ``h`` [T, D]:
        ``(y, pairs)``, ``pairs`` None in a dense layer."""
        if "router" not in block:
            return _gated(h, block["w_gate"], block["w_up"], block["w_down"]), None
        h = h.astype(block["router"].dtype)
        with jax.named_scope("moe_dispatch"):
            chosen, weight = moe_op.route_sigmoid_topk(
                h, block["router"], top_k=self.top_k,
                scale=self.routed_scaling_factor,
                normalize=self.norm_topk_prob, bias=block["router_bias"],
            )
            y, pairs = moe_op.held_experts_apply(
                h, chosen, weight, block["experts_gate"], block["experts_up"],
                block["experts_down"], first_expert=self.first_expert,
                row_mask=row_mask,
            )
        return y + _gated(
            h, block["shared_gate"], block["shared_up"], block["shared_down"]
        ), pairs

    def _block_step(self, block, kind, x, pool, write, table, q_pos,
                    row_mask, *, block_size, lengths, decode):
        b, tq, d = x.shape
        u = rms_norm(x, block["attn_norm"], eps=self.rms_eps)
        update, pool, selection = self._attention(
            block, kind == GLOBAL, u, pool, write, table, q_pos,
            block_size=block_size, lengths=lengths,
        )
        x = x + update
        y, pairs = self._feed_forward(
            block,
            rms_norm(x, block["ffn_norm"], eps=self.rms_eps).reshape(b * tq, d),
            None if row_mask is None else row_mask.reshape(-1),
        )
        return x + y.reshape(b, tq, d), pool, pairs, selection

    def _decode_reads(self, tables, lengths, *, block_size):
        """``cached_rows_by_kind``: the rows ONE layer of each kind read,
        as the form that runs reads them: a full layer each live row's
        length (of which it attends the keys selected:
        ``sparse_selected``), a window layer the window's; ``cached_rows``,
        their mean over the tower's layers."""
        by_kind = {
            GLOBAL: paged_latent_rows_read(
                tables[GLOBAL], lengths, block_size=block_size
            ),
            WINDOW: paged_gqa_rows_read(
                tables[WINDOW], lengths, block_size=block_size,
                window=self.window,
            ),
        }
        return dict(
            cached_rows_by_kind=by_kind,
            cached_rows=_rows_a_layer(by_kind, self.layer_kinds),
        )


def init_params(
    model: SparseLatentMoEModel, *, d_model: int, vocab: int,
    q_lora_rank: int, swa_q_lora_rank: int, v_head_dim: int,
    d_ff_dense: int, d_ff_expert: int, n_routed_experts: int,
    held_experts: int, first_dense: int = 1, seed: int = 0,
    dtype=jnp.float32,
):
    """Seeded gaussian parameters (std ``fan_in ** -0.5``, norm gains 1,
    the router's bias a small gaussian) in the tree the engine takes; for
    tests and examples — a deployment loads its own."""
    rng = np.random.default_rng(seed)

    def normal(*shape, fan_in=None):
        fan_in = fan_in or shape[-2]
        return jnp.asarray(rng.standard_normal(shape) * fan_in ** -0.5, dtype)

    def ones(n):
        return jnp.ones((n,), jnp.float32)

    blocks = []
    for layer, is_full in enumerate(model.full_layers):
        s = model.full if is_full else model.swa
        rank_q = q_lora_rank if is_full else swa_q_lora_rank
        h, dc = s.n_heads, s.kv_lora_rank
        block = {
            "attn_norm": ones(d_model), "wq_a": normal(d_model, rank_q),
            "q_norm": ones(rank_q),
            "wq_b_nope": normal(rank_q, h * s.qk_nope_head_dim),
            "wq_b_rope": normal(rank_q, h * s.qk_rope_head_dim),
            "wkv_a": normal(d_model, dc + s.qk_rope_head_dim),
            "kv_norm": ones(dc),
            "wk_b": normal(dc, h * s.qk_nope_head_dim),
            "wv_b": normal(dc, h * v_head_dim),
            "wg": normal(d_model, h), "wo": normal(h * v_head_dim, d_model),
            "ffn_norm": ones(d_model),
        }
        if is_full:
            di = model.index_head_dim
            block.update(
                wq_idx=normal(rank_q, model.index_n_heads * di),
                wk_idx=normal(d_model, di), k_idx_gain=ones(di),
                k_idx_bias=jnp.asarray(0.1 * rng.standard_normal(di), jnp.float32),
                w_idx=normal(d_model, model.index_n_heads),
            )
        if layer < first_dense:
            block.update(
                w_gate=normal(d_model, d_ff_dense),
                w_up=normal(d_model, d_ff_dense),
                w_down=normal(d_ff_dense, d_model),
            )
        else:
            block.update(
                router=normal(d_model, n_routed_experts),
                router_bias=jnp.asarray(
                    0.1 * rng.standard_normal(n_routed_experts), jnp.float32
                ),
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
