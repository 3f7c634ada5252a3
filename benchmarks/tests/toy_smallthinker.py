"""Toy-size stand-in for ``smallthinker-21b-l8`` on the CPU: the same
kinds of layer (one period of global, window, window, window; grouped
queries; routed ReLU-gated experts, all held) at widths a test run can
hold, with a window of four blocks so that rows cross it."""

from __future__ import annotations

import copy

import toy  # noqa: F401  (puts benchmarks/ on the path)
from harness import loading


def config(**changes) -> dict:
    cfg = copy.deepcopy(loading.load_json("configs", "smallthinker-21b-l8.json"))
    cfg.update(
        name="toy-smallthinker", hidden_size=64, num_hidden_layers=4,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        moe_num_primary_experts=8, moe_num_active_primary_experts=3,
        moe_ffn_hidden_size=32, vocab_size=256, sliding_window_size=32,
        max_position_embeddings=256,
    )
    cfg["serving"] = {
        "max_seq": 256, "block_size": 8, "admit_every": 4, "slots": 8,
        "n_blocks": {"global": 160, "window": 48},
    }
    cfg.update(changes)
    return cfg


def workload(**traffic) -> dict:
    wl = copy.deepcopy(
        loading.load_json("workloads", "smallthinker-serve-mixed-lengths.json")
    )
    wl["traffic"].update(
        arrivals={"process": "poisson", "rate_per_s": 6.0},
        prompt_tokens={"dist": "lognormal", "median": 40, "sigma": 0.8, "min": 10, "max": 160},
        answer_tokens={"dist": "lognormal", "median": 14, "sigma": 0.5, "min": 4, "max": 40},
        ramp_s=0.5,
        # toy limits: on the CPU the toy program (bfloat16 weights, the
        # inputs of every product rounded to bfloat16) reads a mean gap of
        # 0.0010-0.0055 and a widest of 0.13-0.54 over three seeds, the
        # float8-cache control a mean of 0.0117-0.0142 and the
        # float8-products control a mean of 0.08-0.12 and a widest of
        # 1.05-1.50 (PR 31)
        limits={"served_logit_gap_widest": 0.8, "served_logit_gap_mean": 0.008},
    )
    wl["traffic"].update(traffic)
    return wl
