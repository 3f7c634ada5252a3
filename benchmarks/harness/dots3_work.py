"""Bytes and operations the selecting and the windowed latent attention of
``dots3-ep16-l5`` need in a decode step, computed from the configuration
file alone (nothing of the program is imported).  The per-layer readers
divide them by device time from the trace (``harness/dots3_readers.py``)."""

from __future__ import annotations

from harness.axk1_work import least_seconds  # noqa: F401  (the roofline's floor)

BYTES = 2  # bfloat16 weights and cache


def layers_of(cfg) -> dict:
    """How many of the layers that run are of each kind."""
    kinds = cfg["layer_types"][: cfg["num_hidden_layers"]]
    full = sum(k == "full_attention" for k in kinds)
    return {"global": full, "window": len(kinds) - full}


def _sizes(cfg, prefix: str) -> dict:
    return {
        "heads": cfg[prefix + "num_attention_heads"],
        "d_c": cfg[prefix + "kv_lora_rank"],
        "d_n": cfg[prefix + "qk_nope_head_dim"],
        "d_r": cfg[prefix + "qk_rope_head_dim"],
        "d_v": cfg[prefix + "v_head_dim"],
    }


def index_scores(cfg, keys_scored: float) -> dict:
    """The indexer of ONE full layer over ``keys_scored`` (query, key)
    pairs: every key's 128 values read once, and 64 heads x 128 multiply-
    adds a pair."""
    j, d_i = cfg["index_n_heads"], cfg["index_head_dim"]
    return {
        "bytes": keys_scored * d_i * BYTES,
        "flops": 2.0 * keys_scored * j * d_i,
    }


def _absorbed(s: dict, query_rows: float, rows_read: float) -> dict:
    """Absorbed latent attention of ``query_rows`` queries that read
    ``rows_read`` cached rows in all: bytes (each row's latent and rotated
    key once; the folded projections wk_b and wv_b) and FLOPs (fold the
    query, scores over latent + rope, the weighted sum over the latent,
    unfold the output)."""
    fold = 2.0 * query_rows * s["heads"] * s["d_c"] * (s["d_n"] + s["d_v"])
    attend = 2.0 * s["heads"] * rows_read * ((s["d_c"] + s["d_r"]) + s["d_c"])
    return {
        "bytes": rows_read * (s["d_c"] + s["d_r"]) * BYTES
        + BYTES * s["d_c"] * s["heads"] * (s["d_n"] + s["d_v"]),
        "flops": fold + attend,
    }


def sparse_attention(cfg, keys_selected: float) -> dict:
    """ONE full layer's attention over the ``keys_selected`` rows its
    queries kept (1,152 B each).  The queries are taken as ``keys_selected
    / index_topk``: every row of this traffic is past the 2,048 kept."""
    return _absorbed(
        _sizes(cfg, ""), keys_selected / cfg["index_topk"], keys_selected
    )


def window_attention(cfg, rows_read: float) -> dict:
    """ONE window layer's attention over ``rows_read`` cached rows (2,176
    B each); the queries are taken as ``rows_read / sliding_window_size``."""
    return _absorbed(
        _sizes(cfg, "swa_"), rows_read / cfg["sliding_window_size"], rows_read
    )
