"""Plain reference of the ``axk1-ep16`` configuration: the full forward
pass over one sequence in ``jax.numpy``, float32 at ``highest`` matmul
precision, materialised attention only, no cache, no batching, a loop
over the experts held.  It follows ``configs/axk1-ep16.json`` and imports
nothing of the program.

The equations (``rms(x; g) = x * rsqrt(mean(x^2) + eps) * g``):

- attention, ``h = rms(x; attn_norm)``: ``c_q = rms(h wq_a; q_norm)``;
  per head ``q = [c_q wq_b_nope (128), rot(c_q wq_b_rope) (64)]``;
  ``[c_kv (512), k_r (64)] = h wkv_a``; ``c = rms(c_kv; kv_norm)``; the
  row a cache would hold is ``[c, rot(k_r)]``; per head ``k_nope = c
  wk_b``, ``v = c wv_b``; ``score = (q_nope . k_nope + q_rope . rot(k_r))
  * 192^-0.5 * m^2``, ``m = 0.1 ln(factor) + 1``; causal softmax; ``x +=
  concat(p v) wo``.  ``rot`` turns the pairs ``(a[i], a[i + 32])`` by the
  position times the YaRN frequencies (``assumed.rotary_pairs``).
- routed layer, ``h = rms(x; ffn_norm)``: ``s = sigmoid(h router)`` over
  all the published experts; the 8 largest; ``w = scale * s_sel /
  sum(s_sel)``; the part of ``sum_e w_e down_e(silu(gate_e h) * up_e h)``
  that the experts ``[first, first + held)`` give, plus the shared expert.
- layer 0's feed-forward: the same gated form without a router; after the
  last layer ``rms(x; final_norm)`` and the head over the vocabulary slice.

``cast`` rounds both inputs of every matrix product through a lower
precision and back (identity for the reference; the first control passes
float8_e4m3fn, the step below the bfloat16 the configuration states).
``cache_cast`` rounds the row a cache would hold, ``[c, rot(k_r)]``, before
attention reads it (the second control: a latent cache stored in float8).

A 10k-token sequence is computed in row blocks of ``BLOCK``: projections
and feed-forwards a block at a time, attention a block of queries at a
time against every key (K and V of a layer are materialised once)."""

from __future__ import annotations

import json
import math

import jax
import jax.numpy as jnp

BLOCK = 512


def _identity(a):
    return a


def rms(x, gain, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def yarn_inv_freq(cfg):
    """The rotary frequencies as the public DeepSeek-V3 reference code
    computes them (``precompute_freqs_cis``), for the row's
    ``rope_scaling``: [qk_rope_head_dim / 2] float32."""
    dim, base = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    rs = cfg["rope_scaling"]
    factor, orig = float(rs["factor"]), rs["original_max_position_embeddings"]

    def correction_dim(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(correction_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    freqs = 1.0 / base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    ramp = jnp.clip(
        (jnp.arange(dim // 2, dtype=jnp.float32) - low) / (high - low), 0, 1
    )
    smooth = 1 - ramp
    return freqs / factor * (1 - smooth) + freqs * smooth


def softmax_scale(cfg) -> float:
    rs = cfg["rope_scaling"]
    m = 0.1 * rs["mscale_all_dim"] * math.log(rs["factor"]) + 1.0
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5 * m * m


def rot(a, positions, inv_freq):
    """Rotate the pairs ``(a[..., i], a[..., i + half])`` of the last axis;
    ``positions`` indexes the first axis of ``a``."""
    half = a.shape[-1] // 2
    angle = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    shape = (a.shape[0],) + (1,) * (a.ndim - 2) + (half,)
    cos, sin = jnp.cos(angle).reshape(shape), jnp.sin(angle).reshape(shape)
    lo, hi = a[..., :half], a[..., half:]
    return jnp.concatenate([lo * cos - hi * sin, lo * sin + hi * cos], axis=-1)


def _blocks(fn, *arrays):
    """``fn`` over row blocks of ``BLOCK`` (the arrays' first axis is a
    multiple of it), results stacked back."""
    n = arrays[0].shape[0] // BLOCK
    split = tuple(a.reshape((n, BLOCK) + a.shape[1:]) for a in arrays)
    out = jax.lax.map(lambda xs: fn(*xs), split)
    return out.reshape((n * BLOCK,) + out.shape[2:])


def route(cfg, scores):
    """[T, E] sigmoid scores -> (chosen experts [T, k], their weights)."""
    top, idx = jax.lax.top_k(scores, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    return idx, top * cfg["routed_scaling_factor"]


def gated(mm, h, gate, up, down):
    return mm(jax.nn.silu(mm(h, gate)) * mm(h, up), down)


def feed_forward(cfg, b, h, mm, first_expert):
    """The feed-forward of one block on normalised rows ``h`` [T, D]."""
    if "router" not in b:
        return gated(mm, h, b["w_gate"], b["w_up"], b["w_down"])
    scores = jax.nn.sigmoid(mm(h, b["router"]))
    idx, weight = route(cfg, scores)
    y = gated(mm, h, b["shared_gate"], b["shared_up"], b["shared_down"])
    for e in range(b["experts_gate"].shape[0]):
        w_e = jnp.sum(jnp.where(idx == first_expert + e, weight, 0.0), axis=-1)
        y = y + w_e[:, None] * gated(
            mm, h, b["experts_gate"][e], b["experts_up"][e], b["experts_down"][e]
        )
    return y


def attention(cfg, b, x, positions, mm, cast, cache_cast, inv_freq):
    """x [T, D] (T a multiple of BLOCK) -> the attention update [T, D]."""
    eps, n_head = cfg["rms_norm_eps"], cfg["num_attention_heads"]
    d_c, d_n = cfg["kv_lora_rank"], cfg["qk_nope_head_dim"]
    t = x.shape[0]
    scale = softmax_scale(cfg)

    def rows(x_blk, pos_blk):
        h = rms(x_blk, b["attn_norm"], eps)
        kv = mm(h, b["wkv_a"])
        row = jnp.concatenate(
            [rms(kv[:, :d_c], b["kv_norm"], eps), rot(kv[:, d_c:], pos_blk, inv_freq)],
            axis=-1,
        )
        return cache_cast(row)

    cache = _blocks(rows, x, positions)  # [T, d_c + d_r]
    c, k_r = cache[:, :d_c], cache[:, d_c:]
    k_nope = _blocks(lambda c_blk: mm(c_blk, b["wk_b"]), c).reshape(t, n_head, d_n)
    v = _blocks(lambda c_blk: mm(c_blk, b["wv_b"]), c).reshape(t, n_head, -1)
    key_pos = jnp.arange(t)

    def queries(x_blk, pos_blk):
        h = rms(x_blk, b["attn_norm"], eps)
        c_q = rms(mm(h, b["wq_a"]), b["q_norm"], eps)
        q_nope = mm(c_q, b["wq_b_nope"]).reshape(BLOCK, n_head, d_n)
        q_rope = rot(
            mm(c_q, b["wq_b_rope"]).reshape(BLOCK, n_head, -1), pos_blk, inv_freq
        )
        s = jnp.einsum("qhd,khd->hqk", cast(q_nope), cast(k_nope))
        s = s + jnp.einsum("qhd,kd->hqk", cast(q_rope), cast(k_r))
        s = jnp.where(key_pos[None, None, :] <= pos_blk[None, :, None], s * scale, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("hqk,khd->qhd", cast(p), cast(v)).reshape(BLOCK, -1)
        return mm(o, b["wo"])

    return _blocks(queries, x, positions)


def _forward(cfg, w, tokens, cast, cache_cast):
    eps = cfg["rms_norm_eps"]
    first_expert = cfg["deployment"]["first_expert"]
    inv_freq = yarn_inv_freq(cfg)
    positions = jnp.arange(tokens.shape[0])

    def mm(a, b):
        return cast(a.astype(jnp.float32)) @ cast(b.astype(jnp.float32))

    x = w["embed"][tokens].astype(jnp.float32)
    for b in w["blocks"]:
        x = x + attention(cfg, b, x, positions, mm, cast, cache_cast, inv_freq)
        x = x + _blocks(
            lambda x_blk: feed_forward(
                cfg, b, rms(x_blk, b["ffn_norm"], eps), mm, first_expert
            ),
            x,
        )
    return _blocks(lambda x_blk: mm(rms(x_blk, w["final_norm"], eps), w["head"]), x)


_JITTED = {}


def logits(cfg, w, tokens, *, cast=_identity, cache_cast=_identity, pad_to=None):
    """tokens [T] -> logits [T, vocabulary slice] float32.  The sequence
    is padded to ``pad_to`` (rounded up to a multiple of ``BLOCK``; causal
    attention keeps the padding from the rows returned), so requests of
    many lengths can share one compiled program."""
    t = len(tokens)
    padded_len = -(-max(t, pad_to or 0) // BLOCK) * BLOCK
    key = (json.dumps(cfg, sort_keys=True), cast, cache_cast)
    if key not in _JITTED:
        _JITTED[key] = jax.jit(
            lambda w_, t_: _forward(cfg, w_, t_, cast, cache_cast)
        )
    padded = jnp.zeros((padded_len,), jnp.int32).at[:t].set(
        jnp.asarray(tokens, jnp.int32)
    )
    with jax.default_matmul_precision("highest"):
        return _JITTED[key](w, padded)[:t]


def served_gaps(ref_logits, prompt_len: int, served):
    """For each served token, how far its reference logit lies below the
    reference's best at that position (0 where the served token IS the
    reference's greedy choice).  ``ref_logits`` covers prompt + served[:-1]."""
    served = jnp.asarray(served, jnp.int32)
    rows = ref_logits[prompt_len - 1: prompt_len - 1 + served.shape[0]]
    best = jnp.max(rows, axis=-1)
    got = jnp.take_along_axis(rows, served[:, None], axis=1)[:, 0]
    return best - got
