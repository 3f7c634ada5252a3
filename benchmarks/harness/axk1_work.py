"""Bytes and operations a decode step of ``axk1-ep16`` needs, computed
from the configuration file alone (nothing of the program is imported).
The per-layer readers divide them by device time from the trace."""

from __future__ import annotations

BYTES = 2  # bfloat16 weights and cache


def _attention_params(cfg) -> int:
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    d_q, d_c = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    d_n, d_r, d_v = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    return (
        d * d_q + d_q * heads * (d_n + d_r) + d * (d_c + d_r)
        + d_c * heads * (d_n + d_v) + heads * d_v * d
    )


def expert_params(cfg) -> int:
    """One routed expert: gate, up and down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def routed_layers(cfg) -> int:
    return cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]


def always_read_params(cfg) -> int:
    """Every weight a decode step reads whatever the routing: attention of
    every layer, the leading dense feed-forwards, each routed layer's
    router and shared expert, and the head.  The embedding is a gather of
    one row a token and is not counted."""
    d = cfg["hidden_size"]
    dense = cfg["first_k_dense_replace"] * 3 * d * cfg["intermediate_size"]
    per_routed = (
        d * cfg["deployment"]["n_routed_experts_published"]
        + cfg["n_shared_experts"] * expert_params(cfg)
    )
    return (
        cfg["num_hidden_layers"] * _attention_params(cfg) + dense
        + routed_layers(cfg) * per_routed + d * cfg["vocab_size"]
    )


def cache_row_bytes(cfg) -> int:
    """What attention has to read of one cached token in one layer: the
    latent and the rotated key, as published (the 64 zeros the pool pads a
    row with are not work)."""
    return (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * BYTES


def decode_step_bytes(cfg, experts_hit_per_layer: float,
                      rows_gathered: float) -> float:
    """Bytes one decode step must read: the weights of the experts that
    received a token, every other weight once, and the latent rows
    gathered (``rows_gathered`` a layer: slots x window)."""
    layers = cfg["num_hidden_layers"]
    return float(
        BYTES * always_read_params(cfg)
        + BYTES * routed_layers(cfg) * experts_hit_per_layer * expert_params(cfg)
        + layers * rows_gathered * cache_row_bytes(cfg)
    )


def experts_product(cfg, experts_hit: float, pairs: float) -> dict:
    """The grouped expert products of ONE routed layer in one step: bytes
    (weights of the experts hit, the pairs' rows in and out) and FLOPs."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    return {
        "bytes": BYTES * experts_hit * expert_params(cfg)
        + pairs * (BYTES * (d + f) + 4 * (2 * f + d)),
        "flops": 2.0 * pairs * expert_params(cfg),
    }


def absorbed_attention(cfg, rows: float, keys_per_row: float) -> dict:
    """The absorbed attention of ONE layer in one decode step over
    ``rows`` query rows, each against ``keys_per_row`` gathered latent
    rows: bytes (every gathered row once; the folded projections wk_b and
    wv_b) and FLOPs (fold the query, scores over latent + rope, weighted
    sum over the latent, unfold the output)."""
    heads, d_c = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    d_n, d_r, d_v = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    fold = 2.0 * rows * heads * d_c * (d_n + d_v)
    attend = 2.0 * rows * heads * keys_per_row * ((d_c + d_r) + d_c)
    return {
        "bytes": rows * keys_per_row * cache_row_bytes(cfg)
        + BYTES * d_c * heads * (d_n + d_v),
        "flops": fold + attend,
    }


def least_seconds(work: dict, peaks: dict) -> float:
    """The roofline's floor: the larger of bytes over bandwidth and
    operations over the bf16 peak."""
    return max(
        work["bytes"] / peaks["hbm_bytes_per_s"],
        work["flops"] / peaks["bf16_flops"],
    )
