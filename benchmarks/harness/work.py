"""Operations and bytes a call needs, computed from the configuration
files' shapes alone (copied from ``bench.py:_model_flops_per_image`` and
``_lm_train_flops_per_token``; the output-shape arithmetic is inlined so
nothing of the program is imported)."""

from __future__ import annotations


def cnn_forward_flops_per_image(layers, input_shape) -> float:
    """Forward FLOPs (2 x multiply-accumulates) of the conv and
    fully-connected layers of a ``configs/*.json`` layer list; pooling,
    LRN, dropout and activations are not counted."""
    h, w, c = input_shape
    total = 0.0
    flat = None
    for spec in layers:
        kind = spec["type"]
        if kind == "conv":
            k, s, p, n = spec["k"], spec["stride"], spec["pad"], spec["n"]
            h = (h + 2 * p - k) // s + 1
            w = (w + 2 * p - k) // s + 1
            total += 2.0 * h * w * n * k * k * c
            c = n
        elif kind == "max_pool":
            k, s = spec["k"], spec["stride"]
            h = (h - k) // s + 1
            w = (w - k) // s + 1
        elif kind == "fc":
            n_in = flat if flat is not None else h * w * c
            total += 2.0 * n_in * spec["n"]
            flat = spec["n"]
    return total


def lm_matmul_params(cfg) -> int:
    """Weights that take part in a matmul for every token: QKV+O, the FFN
    and the output head (embeddings are gathers)."""
    d, n_layer, v = cfg["n_embd"], cfg["n_layer"], cfg["vocab_size"]
    return n_layer * (4 * d * d + 2 * d * cfg["n_inner"]) + d * v


def lm_forward_flops_per_token(cfg, context: int) -> float:
    """Forward FLOPs of one token that attends ``context`` keys: 2 x the
    matmul weights plus scores and weighted sum, 4 x context x d a layer.
    (``bench.py`` counts training tokens with the causal average T/2 per
    layer and calls the full-T count the bidirectional convention; a
    decode token really attends its whole context, so it is counted.)"""
    d, n_layer = cfg["n_embd"], cfg["n_layer"]
    return 2.0 * lm_matmul_params(cfg) + 4.0 * n_layer * context * d


def lm_decode_bytes_per_step(cfg, rows: int, window_tokens: int) -> float:
    """Bytes one decode step has to read: every weight once, and K and V
    of the gathered window for each row (f32 = 4 bytes)."""
    d, n_layer = cfg["n_embd"], cfg["n_layer"]
    weights = lm_matmul_params(cfg) * 4
    kv = rows * window_tokens * 2 * d * n_layer * 4
    return float(weights + kv)
