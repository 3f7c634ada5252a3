"""Toy-size stand-in for ``dots3-ep16-l5`` on the CPU: the same kinds of
layer (a dense full layer, a routed full layer, three window layers; an
indexer that keeps 16 keys; head-wise gates; sigmoid scores with a bias;
half of 16 experts held) at widths a test run can hold, with a window of 9
keys at a block of 8 so that every row crosses both."""

from __future__ import annotations

import copy

import toy  # noqa: F401  (puts benchmarks/ on the path)
from harness import loading


def config(**changes) -> dict:
    cfg = copy.deepcopy(loading.load_json("configs", "dots3-ep16-l5.json"))
    cfg.update(
        name="toy-dots3", hidden_size=64, intermediate_size=96,
        moe_intermediate_size=32, num_attention_heads=4, q_lora_rank=32,
        kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, swa_num_attention_heads=2, swa_q_lora_rank=32,
        swa_kv_lora_rank=32, swa_qk_nope_head_dim=24, swa_qk_rope_head_dim=8,
        swa_v_head_dim=16, sliding_window_size=9, index_n_heads=4,
        index_head_dim=16, index_topk=16, n_routed_experts=8,
        num_experts_per_tok=3, vocab_size=256, max_position_embeddings=256,
    )
    cfg["deployment"] = dict(
        cfg["deployment"], first_expert=4, n_routed_experts_published=16
    )
    cfg["serving"] = {
        "max_seq": 256, "block_size": 8, "admit_every": 4, "slots": 8,
        "prefill_budget": 32, "n_blocks": {"global": 160, "window": 48},
    }
    # at these widths the rescale spreads the logits less: no damping
    cfg["assumed"] = dict(cfg["assumed"], query_gain=1.0)
    cfg.update(changes)
    return cfg


def workload(**traffic) -> dict:
    wl = copy.deepcopy(loading.load_json("workloads", "dots3-serve-long-docs.json"))
    wl["traffic"].update(
        arrivals={"process": "poisson", "rate_per_s": 5.0},
        prompt_tokens={"dist": "lognormal", "median": 60, "sigma": 0.6, "min": 20, "max": 160},
        answer_tokens={"dist": "lognormal", "median": 12, "sigma": 0.5, "min": 4, "max": 32},
        ramp_s=0.5, check_requests=6,
        limits=TOY_LIMITS,
    )
    wl["traffic"].update(traffic)
    return wl


# toy limits, from readings on the CPU over three seeds (bfloat16 weights,
# the inputs of every product rounded to bfloat16; an indexer of 4 heads
# that keeps 16 of ~100 keys, so a rounding moves a kept key far more often
# than at the published sizes): the program reads a mean gap of 0.07-0.18
# and a widest of 1.36-2.35; the controls read means of 0.94-1.19 (float8
# products), 1.71-2.05 (half the keys kept), 1.88-2.27 (every key) and
# 2.25-2.51 (the most recent) and a widest of 3.44 at least (PR 36)
TOY_LIMITS = {
    "served_logit_gap_widest": 3.0, "served_logit_gap_mean": 0.5,
    "selected_keys_not_shared_mean": 0.05,
}
