"""Bytes and operations the selecting grouped-query attention and the
SiLU-gated experts of ``keye-vl2-30b-a3b-l6`` need in a decode step,
computed from the configuration file alone (nothing of the program is
imported): the same work whatever implements the kernel.  The per-layer
readers divide them by device time from the trace
(``harness/keye_readers.py``)."""

from __future__ import annotations

from harness.axk1_work import least_seconds  # noqa: F401  (the roofline's floor)

BYTES = 2  # bfloat16 weights and cache


def expert_params(cfg) -> int:
    """One expert: gate, up and down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def cache_row_bytes(cfg) -> int:
    """What attention reads of one kept token in one layer: K and V of the
    K/V heads."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * BYTES


def index_scores(cfg, keys_scored: float) -> dict:
    """The indexer of ONE layer over ``keys_scored`` (query, key) pairs:
    every key's 64 values read once, and 16 heads x 64 multiply-adds a
    pair."""
    sa = cfg["sa_config"]
    j, d_i = sa["indexer_num_heads"], sa["indexer_head_dim"]
    return {
        "bytes": keys_scored * d_i * BYTES,
        "flops": 2.0 * keys_scored * j * d_i,
    }


def kept_attention(cfg, keys_selected: float) -> dict:
    """ONE layer's attention over the ``keys_selected`` rows its queries
    kept: each row's K and V once (2,048 B) and every query head against
    the key and the value of its K/V head: what the selection makes
    NECESSARY, whatever the kernel fetches."""
    heads, dim = cfg["num_attention_heads"], cfg["head_dim"]
    return {
        "bytes": keys_selected * cache_row_bytes(cfg),
        "flops": 2.0 * 2.0 * heads * dim * keys_selected,
    }


def experts_product(cfg, experts_hit: float, pairs: float) -> dict:
    """The grouped expert products of ONE layer in one step: bytes
    (weights of the experts hit, the pairs' rows in and out) and FLOPs."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    return {
        "bytes": BYTES * experts_hit * expert_params(cfg)
        + pairs * (BYTES * (d + f) + 4 * (2 * f + d)),
        "flops": 2.0 * pairs * expert_params(cfg),
    }
