"""Bytes and operations a decode step of ``smallthinker-21b-l8`` needs,
computed from the configuration file alone (nothing of the program is
imported).  The per-layer readers divide them by device time from the
trace (``harness/smallthinker_readers.py``)."""

from __future__ import annotations

from harness.axk1_work import least_seconds  # noqa: F401  (the roofline's floor)

BYTES = 2  # bfloat16 weights and cache


def layers_of(cfg) -> dict:
    """How many of the layers that run are of each kind."""
    window = sum(cfg["sliding_window_layout"][: cfg["num_hidden_layers"]])
    return {"window": window, "global": cfg["num_hidden_layers"] - window}


def attention_params(cfg) -> int:
    d, dim = cfg["hidden_size"], cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * dim, cfg["num_key_value_heads"] * dim
    return d * q + 2 * d * kv + q * d


def expert_params(cfg) -> int:
    """One expert: gate, up and down."""
    return 3 * cfg["hidden_size"] * cfg["moe_ffn_hidden_size"]


def always_read_params(cfg) -> int:
    """Every weight a decode step reads whatever the routing: attention
    and router of every layer, and the head.  The embedding is a gather of
    one row a token and is not counted."""
    d = cfg["hidden_size"]
    per_layer = attention_params(cfg) + d * cfg["moe_num_primary_experts"]
    return cfg["num_hidden_layers"] * per_layer + d * cfg["vocab_size"]


def cache_row_bytes(cfg) -> int:
    """What attention reads of one cached token in one layer: K and V of
    the K/V heads."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * BYTES


def resident_token_bytes(cfg, length: float) -> float:
    """Pool bytes a resident token costs in a row of ``length`` tokens,
    blocks apart: every global layer keeps it, a window layer the last
    window's (one table a row would keep it in every layer)."""
    layers = layers_of(cfg)
    kept = min(1.0, cfg["sliding_window_size"] / length)
    return cache_row_bytes(cfg) * (layers["global"] + layers["window"] * kept)


def gqa_attention(cfg, rows_read: float) -> dict:
    """The grouped-query attention of ONE layer in one decode step that
    reads ``rows_read`` cached rows in all (summed over the query rows):
    bytes (every row once) and FLOPs (every query head against the keys of
    its K/V head, then the weighted sum of the values)."""
    heads, dim = cfg["num_attention_heads"], cfg["head_dim"]
    return {
        "bytes": rows_read * cache_row_bytes(cfg),
        "flops": 2.0 * 2.0 * heads * dim * rows_read,
    }


def experts_product(cfg, experts_hit: float, pairs: float) -> dict:
    """The grouped expert products of ONE layer in one step: bytes
    (weights of the experts hit, the pairs' rows in and out) and FLOPs."""
    d, f = cfg["hidden_size"], cfg["moe_ffn_hidden_size"]
    return {
        "bytes": BYTES * experts_hit * expert_params(cfg)
        + pairs * (BYTES * (d + f) + 4 * (2 * f + d)),
        "flops": 2.0 * pairs * expert_params(cfg),
    }


def decode_step_bytes(cfg, experts_hit_per_layer: float,
                      rows_read: dict) -> float:
    """Bytes one decode step must read: the weights of the experts that
    received a token, every other weight once, and the cached rows read
    (``rows_read``: by kind, a layer of it)."""
    layers = layers_of(cfg)
    return float(
        BYTES * always_read_params(cfg)
        + BYTES * cfg["num_hidden_layers"] * experts_hit_per_layer * expert_params(cfg)
        + sum(layers[k] * rows_read[k] for k in layers) * cache_row_bytes(cfg)
    )
