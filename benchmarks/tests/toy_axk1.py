"""Toy-size stand-in for ``axk1-ep16`` on the CPU: the same kinds of
layer (latent attention with a rotary part, a leading dense layer, routed
experts of which a share is held, a shared expert, a sliced head) at
widths a test run can hold."""

from __future__ import annotations

import copy

import toy  # noqa: F401  (puts benchmarks/ on the path)
from harness import loading


def config(**changes) -> dict:
    cfg = copy.deepcopy(loading.load_json("configs", "axk1-ep16.json"))
    cfg.update(
        name="toy-axk1", hidden_size=64, intermediate_size=96,
        moe_intermediate_size=32, num_hidden_layers=4, num_attention_heads=4,
        q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=8,
        qk_rope_head_dim=8, v_head_dim=8, n_routed_experts=4,
        num_experts_per_tok=4, vocab_size=256, max_position_embeddings=512,
    )
    cfg["rope_scaling"] = dict(
        cfg["rope_scaling"], original_max_position_embeddings=16
    )
    cfg["deployment"] = dict(
        cfg["deployment"], first_expert=4, n_routed_experts_published=16
    )
    cfg["serving"] = {
        "max_seq": 128, "block_size": 8, "admit_every": 4, "slots": 8,
        "n_blocks": 96,
    }
    cfg.update(changes)
    return cfg


def workload(**traffic) -> dict:
    wl = copy.deepcopy(loading.load_json("workloads", "axk1-serve-shared-doc.json"))
    wl["traffic"].update(
        arrivals={"process": "poisson", "rate_per_s": 6.0},
        shared_prefix_tokens=32,
        prompt_tokens={"dist": "lognormal", "median": 48, "sigma": 0.2, "min": 36, "max": 72},
        answer_tokens={"dist": "lognormal", "median": 16, "sigma": 0.5, "min": 4, "max": 32},
        ramp_s=0.5,
        # toy limits: on the CPU the toy program (bfloat16 weights, the
        # inputs of every product rounded to bfloat16) reads a mean gap of
        # 0.0005-0.004 and a widest of 0.03-0.27 over three seeds, the
        # float8-cache control a mean of 0.02-0.09 and the float8-products
        # control 0.07-0.23 (PR 26)
        limits={"served_logit_gap_widest": 0.4, "served_logit_gap_mean": 0.01},
    )
    wl["traffic"].update(traffic)
    return wl
