"""Toy-size stand-in for ``keye-vl2-30b-a3b-l6`` on the CPU: the same layer
(grouped-query attention over the 16 keys an indexer of 4 heads keeps, 16
softmax-routed experts all held, 3 a token) at widths a test run can hold,
and the same traffic in small: a shared prefix of 64 tokens at a block of
8, primed into the prefix cache, own turns and answers of a dozen tokens."""

from __future__ import annotations

import copy

import toy  # noqa: F401  (puts benchmarks/ on the path)
from harness import loading


def config(**changes) -> dict:
    cfg = copy.deepcopy(loading.load_json("configs", "keye-vl2-30b-a3b-l6.json"))
    cfg.update(
        name="toy-keye", hidden_size=64, moe_intermediate_size=32,
        num_attention_heads=8, num_key_value_heads=2, head_dim=16,
        num_hidden_layers=3, num_experts=16, num_local_experts=16,
        num_experts_per_tok=3, vocab_size=256, max_position_embeddings=256,
    )
    cfg["sa_config"] = dict(
        cfg["sa_config"], indexer_head_dim=8, indexer_num_heads=4, topk=16
    )
    cfg["serving"] = {
        "max_seq": 256, "block_size": 8, "admit_every": 4, "slots": 8,
        "prefill_budget": 32, "n_blocks": {"global": 160},
    }
    cfg.update(changes)
    return cfg


def workload(**traffic) -> dict:
    wl = copy.deepcopy(
        loading.load_json("workloads", "keye-serve-shared-long-context.json")
    )
    wl["traffic"].update(
        arrivals={"process": "poisson", "rate_per_s": 5.0},
        shared_prefix_tokens=64,
        prompt_tokens={"dist": "lognormal", "median": 84, "sigma": 0.1, "min": 68, "max": 140},
        answer_tokens={"dist": "lognormal", "median": 12, "sigma": 0.5, "min": 4, "max": 32},
        ramp_s=0.5, check_requests=6, limits=TOY_LIMITS,
    )
    wl["traffic"].update(traffic)
    return wl


# toy limits, from readings on the CPU (bfloat16 weights, the inputs of
# every product rounded to bfloat16; an indexer of 4 heads that keeps 16 of
# ~100 keys), seeds 77, 78 and 3000000005 (PR 41): the program reads a mean
# gap of 0.007 on each and a widest of 0.27-0.47; the controls read means of
# 0.043-0.063 (float8 products), 0.14-0.22 (every key), 0.18-0.20 (half the
# keys kept), 0.25-0.35 (the most recent) and 0.24-0.27 (the prefix's
# indexer keys zeros); their widest gaps are 1.0 at least but for float8's
# 0.38-0.75, which fails by the mean alone
TOY_LIMITS = {
    "served_logit_gap_widest": 0.85, "served_logit_gap_mean": 0.02,
    "selected_keys_not_shared_mean": 0.06,
}
