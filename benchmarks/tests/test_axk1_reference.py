"""``reference/axk1.py`` against values computed by hand: the same
equations written out in numpy float64 with plain loops over positions,
heads and experts (no blocks, no ``jax``), at toy size."""

import math

import numpy as np

import toy_axk1
from harness import axk1_weights, loading
from harness.loading import load_module


def _rms(x, g, eps):
    return x / np.sqrt(np.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _inv_freq(cfg):
    dim, base, rs = cfg["qk_rope_head_dim"], cfg["rope_theta"], cfg["rope_scaling"]
    orig = rs["original_max_position_embeddings"]

    def corr(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) / (2 * math.log(base))

    low, high = max(math.floor(corr(rs["beta_fast"])), 0), min(math.ceil(corr(rs["beta_slow"])), dim - 1)
    out = []
    for i in range(dim // 2):
        f = base ** (-2.0 * i / dim)
        ramp = min(max((i - low) / ((high - low) or 0.001), 0.0), 1.0)
        out.append(f * (1 - ramp) + f / rs["factor"] * ramp)
    return np.array(out)


def _rot(a, pos, inv_freq):
    half = a.shape[-1] // 2
    out = np.array(a, np.float64)
    for i in range(half):
        c, s = math.cos(pos * inv_freq[i]), math.sin(pos * inv_freq[i])
        out[..., i] = a[..., i] * c - a[..., i + half] * s
        out[..., i + half] = a[..., i] * s + a[..., i + half] * c
    return out


def _silu(x):
    return x / (1 + np.exp(-x))


def _by_hand(cfg, w, tokens):
    f64 = lambda a: np.asarray(a, np.float64)  # noqa: E731
    eps, heads = cfg["rms_norm_eps"], cfg["num_attention_heads"]
    d_c, d_n, d_v = cfg["kv_lora_rank"], cfg["qk_nope_head_dim"], cfg["v_head_dim"]
    inv_freq = _inv_freq(cfg)
    rs = cfg["rope_scaling"]
    m = 0.1 * rs["mscale_all_dim"] * math.log(rs["factor"]) + 1
    scale = (d_n + cfg["qk_rope_head_dim"]) ** -0.5 * m * m
    first = cfg["deployment"]["first_expert"]
    x = f64(w["embed"])[np.asarray(tokens)]
    t = len(tokens)
    for b in w["blocks"]:
        b = {k: f64(v) for k, v in b.items()}
        h = _rms(x, b["attn_norm"], eps)
        cache = []
        for p in range(t):
            kv = h[p] @ b["wkv_a"]
            cache.append((_rms(kv[:d_c], b["kv_norm"], eps), _rot(kv[d_c:], p, inv_freq)))
        update = np.zeros_like(x)
        for p in range(t):
            c_q = _rms(h[p] @ b["wq_a"], b["q_norm"], eps)
            q_nope = (c_q @ b["wq_b_nope"]).reshape(heads, d_n)
            q_rope = _rot((c_q @ b["wq_b_rope"]).reshape(heads, -1), p, inv_freq)
            out = []
            for head in range(heads):
                wk = b["wk_b"][:, head * d_n:(head + 1) * d_n]
                wv = b["wv_b"][:, head * d_v:(head + 1) * d_v]
                scores = np.array([
                    (q_nope[head] @ (c @ wk) + q_rope[head] @ k_r) * scale
                    for c, k_r in cache[: p + 1]
                ])
                prob = np.exp(scores - scores.max())
                prob /= prob.sum()
                out.append(sum(pr * (c @ wv) for pr, (c, _) in zip(prob, cache)))
            update[p] = np.concatenate(out) @ b["wo"]
        x = x + update
        h = _rms(x, b["ffn_norm"], eps)
        if "router" not in b:
            y = (_silu(h @ b["w_gate"]) * (h @ b["w_up"])) @ b["w_down"]
        else:
            y = (_silu(h @ b["shared_gate"]) * (h @ b["shared_up"])) @ b["shared_down"]
            for p in range(t):
                s = 1 / (1 + np.exp(-(h[p] @ b["router"])))
                top = np.argsort(-s)[: cfg["num_experts_per_tok"]]
                weights = cfg["routed_scaling_factor"] * s[top] / s[top].sum()
                for e, w_e in zip(top, weights):
                    if first <= e < first + b["experts_gate"].shape[0]:
                        g = e - first
                        y[p] += w_e * (
                            (_silu(h[p] @ b["experts_gate"][g]) * (h[p] @ b["experts_up"][g]))
                            @ b["experts_down"][g]
                        )
        x = x + y
    return _rms(x, f64(w["final_norm"]), eps) @ f64(w["head"])


def test_reference_matches_values_computed_by_hand():
    cfg = toy_axk1.config()
    w = axk1_weights.weights(cfg, 11)
    ref = load_module("reference", "axk1")
    tokens = np.random.default_rng(3).integers(1, cfg["vocab_size"], 13)
    got = np.asarray(ref.logits(cfg, w, tokens.tolist()))
    want = _by_hand(cfg, w, tokens)
    assert got.shape == (13, cfg["vocab_size"])
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_the_weights_are_the_share_the_configuration_states():
    cfg = loading.load_json("configs", "axk1-ep16.json")
    assert axk1_weights.n_parameters(cfg) - _norm_gains(cfg) == cfg["parameters_held"]["total"]
    toy = toy_axk1.config()
    w = axk1_weights.weights(toy, 5)
    again = axk1_weights.weights(toy, 5)
    other = axk1_weights.weights(toy, 3000000005)
    assert str(w["head"].dtype) == "bfloat16" and str(w["final_norm"].dtype) == "float32"
    assert np.array_equal(np.asarray(w["head"], np.float32), np.asarray(again["head"], np.float32))
    assert not np.array_equal(np.asarray(w["head"], np.float32), np.asarray(other["head"], np.float32))
    routed = w["blocks"][1]
    assert routed["router"].shape == (64, 16) and routed["experts_gate"].shape == (4, 64, 32)
    assert "router" not in w["blocks"][0]


def _norm_gains(cfg):
    d = cfg["hidden_size"]
    per_layer = 2 * d + cfg["q_lora_rank"] + cfg["kv_lora_rank"]
    return cfg["num_hidden_layers"] * per_layer + d
