"""Toy-size stand-in for ``laguna-xs2-stage1`` on the CPU: the same layers
(a dense full layer, then sliding, sliding, full; 12 query heads on the full
layers and 16 on the sliding ones over 2 K/V heads: groups of 6 and of 8; a
gate a head; 16 sigmoid-routed experts beside a shared one) at widths a test
run can hold, and the same traffic in small: a shared prefix of 64 tokens at
a block of 8 behind a window of 24 keys, primed into the prefix cache asked
for by name, own turns and answers of a dozen tokens."""

from __future__ import annotations

import copy

import toy  # noqa: F401  (puts benchmarks/ on the path)
from harness import loading

FULL, SLIDING = "full_attention", "sliding_attention"


def config(**changes) -> dict:
    cfg = copy.deepcopy(loading.load_json("configs", "laguna-xs2-stage1.json"))
    cfg.update(
        name="toy-laguna", hidden_size=64, intermediate_size=96,
        moe_intermediate_size=32, shared_expert_intermediate_size=32,
        num_attention_heads=12, num_key_value_heads=2, head_dim=16,
        num_hidden_layers=4, num_experts=16, num_experts_per_tok=3,
        vocab_size=256, max_position_embeddings=256, sliding_window=24,
        layer_types=[FULL, SLIDING, SLIDING, FULL],
        mlp_layer_types=["dense", "sparse", "sparse", "sparse"],
        num_attention_heads_per_layer=[12, 16, 16, 12],
    )
    cfg["rope_parameters"] = {
        FULL: dict(
            cfg["rope_parameters"][FULL], factor=8,
            original_max_position_embeddings=32, beta_fast=4,
            attention_factor=1.2079,
        ),
        SLIDING: cfg["rope_parameters"][SLIDING],
    }
    cfg["serving"] = {
        "max_seq": 256, "block_size": 8, "admit_every": 4, "slots": 8,
        "prefill_budget": 32, "prefix_cache": True,
        "n_blocks": {"global": 160, "window": 48},
    }
    cfg.update(changes)
    return cfg


def workload(**traffic) -> dict:
    wl = copy.deepcopy(loading.load_json("workloads", "laguna-serve-agent-turns.json"))
    wl["traffic"].update(
        arrivals={"process": "poisson", "rate_per_s": 5.0},
        shared_prefix_tokens=64,
        prompt_tokens={"dist": "lognormal", "median": 84, "sigma": 0.15, "min": 68, "max": 160},
        answer_tokens={"dist": "lognormal", "median": 12, "sigma": 0.5, "min": 4, "max": 32},
        ramp_s=0.5, check_requests=6, limits=TOY_LIMITS,
    )
    wl["traffic"].update(traffic)
    return wl


# toy limits, from readings on the CPU (bfloat16 weights, the inputs of
# every product rounded to bfloat16), seeds 77, 78 and 3000000005 (PR 44):
# the program reads a mean gap of 0.0007-0.0061 and a widest of 0.04-0.46;
# the controls read means of 0.027-0.038 (the cached K and V in float8),
# 0.041-0.071 (float8 products), 0.23-0.38 (the window doubled), 0.29-0.38
# (plain rotary on the full layers) and 0.63-0.82 (no gate): every one fails
# the mean; float8's widest (0.40 at the least) can pass its limit
TOY_LIMITS = {"served_logit_gap_widest": 1.0, "served_logit_gap_mean": 0.015}
