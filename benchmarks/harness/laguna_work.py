"""Bytes and operations a decode step of ``laguna-xs2-stage1`` needs,
computed from the configuration file alone (nothing of the program is
imported): the same work whatever implements a kernel.  The per-layer
readers divide them by device time from the trace
(``harness/laguna_readers.py``)."""

from __future__ import annotations

from harness.axk1_work import least_seconds  # noqa: F401  (the roofline's floor)

BYTES = 2  # bfloat16 weights and cache
KINDS = {"global": "full_attention", "window": "sliding_attention"}


def _layers(cfg):
    n = cfg["num_hidden_layers"]
    return list(zip(
        cfg["layer_types"][:n], cfg["mlp_layer_types"][:n],
        cfg["num_attention_heads_per_layer"][:n],
    ))


def layers_of(cfg) -> dict:
    """How many of the layers that run are of each cache kind."""
    return {
        kind: sum(1 for t, _, _ in _layers(cfg) if t == name)
        for kind, name in KINDS.items()
    }


def heads_of(cfg, kind: str) -> int:
    """Query heads of a layer of the cache kind."""
    return next(h for t, _, h in _layers(cfg) if t == KINDS[kind])


def routed_layers(cfg) -> int:
    return sum(1 for _, mlp, _ in _layers(cfg) if mlp == "sparse")


def attention_params(cfg, heads: int) -> int:
    """q, k, v, the gate a head and o of one layer."""
    d, dim = cfg["hidden_size"], cfg["head_dim"]
    q, kv = heads * dim, cfg["num_key_value_heads"] * dim
    return d * q + 2 * d * kv + d * heads + q * d


def expert_params(cfg) -> int:
    """One routed expert: gate, up and down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def always_read_params(cfg) -> int:
    """Every weight a decode step reads whatever the routing: attention of
    every layer, the dense feed-forward, each routed layer's router and
    shared expert, and the head.  The embedding is a gather of one row a
    token and is not counted."""
    d = cfg["hidden_size"]
    total = d * cfg["vocab_size"]
    for _, mlp, heads in _layers(cfg):
        total += attention_params(cfg, heads)
        if mlp == "dense":
            total += 3 * d * cfg["intermediate_size"]
        else:
            total += d * cfg["num_experts"] + 3 * d * cfg["shared_expert_intermediate_size"]
    return total


def cache_row_bytes(cfg) -> int:
    """What attention reads of one cached token in one layer: K and V of
    the K/V heads."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * BYTES


def gqa_attention(cfg, kind: str, rows_read: float) -> dict:
    """The grouped-query attention of ONE layer of the kind in one decode
    step that reads ``rows_read`` cached rows in all: bytes (every row
    once) and FLOPs (every query head against the key, then the value, of
    its K/V head: 4 x heads x 128 a row)."""
    return {
        "bytes": rows_read * cache_row_bytes(cfg),
        "flops": 2.0 * 2.0 * heads_of(cfg, kind) * cfg["head_dim"] * rows_read,
    }


def experts_product(cfg, experts_hit: float, pairs: float) -> dict:
    """The grouped expert products of ONE routed layer in one step: bytes
    (weights of the experts hit, the pairs' rows in and out) and FLOPs."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    return {
        "bytes": BYTES * experts_hit * expert_params(cfg)
        + pairs * (BYTES * (d + f) + 4 * (2 * f + d)),
        "flops": 2.0 * pairs * expert_params(cfg),
    }


def decode_step_bytes(cfg, experts_hit_per_layer: float, rows_read: dict) -> float:
    """Bytes one decode step must read: every non-expert weight once, the
    weights of the experts that received a token, and the cached rows read
    (``rows_read``: by kind, a layer of it)."""
    layers = layers_of(cfg)
    return float(
        BYTES * always_read_params(cfg)
        + BYTES * routed_layers(cfg) * experts_hit_per_layer * expert_params(cfg)
        + sum(layers[k] * rows_read[k] for k in layers) * cache_row_bytes(cfg)
    )
