"""Plain reference of the ``laguna-xs2-stage1`` configuration: the forward
pass over one sequence in ``jax.numpy``, float32 at ``highest`` matmul
precision, no cache, no kernels, no batching, masks built from positions,
every expert over every row.  It follows ``configs/laguna-xs2-stage1.json``
and imports nothing of the program.

The equations (``rms(x; g) = x * rsqrt(mean(x^2) + eps) * g``), layer ``l``
of type ``t = layer_types[l]`` with ``H = num_attention_heads_per_layer[l]``
query heads (64 sliding, 48 full) over 8 K/V heads of 128:

- ``u = rms(x; attn_norm)``; ``q = u wq`` [H x 128], ``k = u wk``, ``v = u
  wv`` [8 x 128], no biases, no q/k norms;
- SLIDING: q and k turned over the whole head by the position times ``10000
  ** (-2i / 128)``, the pairs ``(a[i], a[i + 64])``; key ``j`` is visible to
  query ``i`` iff ``i - 512 < j <= i``.  FULL: the first 64 values of each
  head turned (``partial_rotary_factor`` 0.5; the pairs ``(a[i], a[i +
  32])``) by YaRN's frequencies (theta 500,000, factor 64 over 4,096,
  ``beta_fast`` 64, ``beta_slow`` 1), their cosines and sines multiplied by
  ``attention_factor``; the other 64 as they are; key ``j`` visible iff ``j
  <= i``;
- ``o = softmax(q k^T / sqrt(128)) v``, query head ``h`` reading K/V head
  ``h // (H / 8)``; ``g = sigmoid(u wg)`` [H], one value a head; ``x1 = x +
  (o * g) wo``;
- ``h = rms(x1; ffn_norm)``.  A ``dense`` layer: ``x2 = x1 + (silu(h w_gate)
  * (h w_up)) w_down``.  A ``sparse`` one: ``s = sigmoid(h router)`` over the
  256 experts, the 8 largest of ``s + router_bias``, ``w = 2.5 * s / sum(s)``
  over those 8, ``x2 = x1 + sum_e w_e expert_e(h) + shared(h)``, both
  SiLU-gated;
- after the last layer ``rms(x; final_norm)`` and the untied head.

What the configuration does not say and this file assumes is listed under
``assumed`` in the configuration file.

Causality makes the layers' keys and values of a SHARED PREFIX the same for
every request that opens with it: :func:`prefix_state` computes them once a
seed (the sliding layers' last ``window - 1`` alone) and :func:`logits`
computes a request's own tail against them, queries in blocks of ``BLOCK``
against every key.

The controls (what a run's ``correct`` must catch) are arguments: ``cast``
rounds both inputs of every matrix product through a lower precision and
back; ``cache_cast`` rounds what a cache would hold, the turned ``k`` and
``v``; ``gate=False`` leaves the head-wise gate out; ``window`` reads another
window than the configuration's; ``full_rope="plain"`` turns the full
layers' 64 values by the plain frequencies of their theta, unscaled."""

from __future__ import annotations

import json
import math

import jax
import jax.numpy as jnp

BLOCK = 128
FULL, SLIDING = "full_attention", "sliding_attention"


def _identity(a):
    return a


def rms(x, gain, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def plain_freqs(dim, theta):
    return 1.0 / float(theta) ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)


def yarn_freqs(dim, rope):
    """[dim / 2] float32: the plain frequencies for pairs that turn more
    than ``beta_fast`` times within the original context, those over
    ``factor`` for pairs that turn fewer than ``beta_slow`` times, a linear
    ramp between (Peng et al. 2023, as transformers computes them)."""
    base, original = float(rope["rope_theta"]), rope["original_max_position_embeddings"]

    def correction_dim(rotations):
        return dim * math.log(original / (rotations * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(correction_dim(rope["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rope["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low) / (high - low), 0, 1)
    plain = plain_freqs(dim, base)
    return plain / rope["factor"] * ramp + plain * (1 - ramp)


def rot(a, positions, freqs, scale=1.0):
    """Rotate the pairs ``(a[..., i], a[..., i + half])`` of the leading
    ``2 * len(freqs)`` values of the last axis, cosines and sines times
    ``scale``; the rest as it is.  ``positions`` indexes the first axis of
    ``a`` [T, heads, dim]."""
    half = freqs.shape[0]
    angle = positions.astype(jnp.float32)[:, None] * freqs[None, :]
    cos, sin = scale * jnp.cos(angle)[:, None, :], scale * jnp.sin(angle)[:, None, :]
    lo, hi, rest = a[..., :half], a[..., half:2 * half], a[..., 2 * half:]
    return jnp.concatenate([lo * cos - hi * sin, lo * sin + hi * cos, rest], axis=-1)


def _blocks(fn, *arrays):
    """``fn`` over row blocks of ``BLOCK`` (or, where the arrays' first
    axis is no multiple of it, of their largest common divisor: a toy
    prefix), results stacked back."""
    rows = arrays[0].shape[0]
    block = math.gcd(rows, BLOCK)
    split = tuple(a.reshape((rows // block, block) + a.shape[1:]) for a in arrays)
    out = jax.lax.map(lambda xs: fn(*xs), split)
    return out.reshape((rows,) + out.shape[2:])


def route(cfg, b, h, mm):
    """Normalised rows ``h`` [T, D] -> ``w`` [T, E]: an expert's weight
    where it was chosen, 0 elsewhere."""
    s = jax.nn.sigmoid(mm(h, b["router"]))
    _, idx = jax.lax.top_k(s + b["router_bias"], cfg["num_experts_per_tok"])
    chosen = jnp.sum(jax.nn.one_hot(idx, s.shape[-1], dtype=jnp.float32), axis=1)
    picked = s * chosen
    return cfg["moe_routed_scaling_factor"] * picked / jnp.sum(
        picked, axis=-1, keepdims=True
    )


def gated(h, gate, up, down, mm):
    return mm(jax.nn.silu(mm(h, gate)) * mm(h, up), down)


def feed_forward(cfg, b, h, mm):
    if "router" not in b:
        return gated(h, b["w_gate"], b["w_up"], b["w_down"], mm)
    weight = route(cfg, b, h, mm)

    def expert(y, e):
        w_e, gate, up, down = e
        return y + w_e[:, None] * gated(h, gate, up, down, mm), None

    y, _ = jax.lax.scan(
        expert, jnp.zeros_like(h),
        (weight.T, b["experts_gate"], b["experts_up"], b["experts_down"]),
    )
    return y + gated(h, b["shared_gate"], b["shared_up"], b["shared_down"], mm)


def layer(cfg, b, kind, heads, x, positions, past, opts):
    """One layer over ``x`` [T, D] at ``positions``
    [T]; ``past`` the ``(k, v)`` [P, 8, 128] of the positions ``positions[0]
    - P ..`` before them.  Returns ``(x, (k, v))``, the keys and values of
    ``past`` and these rows together."""
    mm, cast, cache_cast = opts["mm"], opts["cast"], opts["cache_cast"]
    eps, dim = cfg["rms_norm_eps"], cfg["head_dim"]
    groups = cfg["num_key_value_heads"]
    t = x.shape[0]
    rope = cfg["rope_parameters"][kind]
    if kind == SLIDING:
        freqs, scale = plain_freqs(dim, rope["rope_theta"]), 1.0
    elif opts["full_rope"] == "plain":
        freqs = plain_freqs(int(dim * rope["partial_rotary_factor"]), rope["rope_theta"])
        scale = 1.0
    else:
        freqs = yarn_freqs(int(dim * rope["partial_rotary_factor"]), rope)
        scale = rope["attention_factor"]
    u = rms(x, b["attn_norm"], eps)
    k = cache_cast(rot(mm(u, b["wk"]).reshape(t, groups, dim), positions, freqs, scale))
    v = cache_cast(mm(u, b["wv"]).reshape(t, groups, dim))
    if past is not None:
        k, v = jnp.concatenate([past[0], k]), jnp.concatenate([past[1], v])
    key_pos = positions[0] - (k.shape[0] - t) + jnp.arange(k.shape[0])

    def attend(u_blk, pos_blk):
        n = u_blk.shape[0]
        q = rot(mm(u_blk, b["wq"]).reshape(n, heads, dim), pos_blk, freqs, scale)
        q = q.reshape(n, groups, heads // groups, dim)
        s = jnp.einsum("qgjd,kgd->gjqk", cast(q), cast(k)) * dim ** -0.5
        see = key_pos[None, :] <= pos_blk[:, None]
        if kind == SLIDING:
            see = see & (key_pos[None, :] > pos_blk[:, None] - opts["window"])
        p = jax.nn.softmax(jnp.where(see[None, None], s, -jnp.inf), axis=-1)
        o = jnp.einsum("gjqk,kgd->qgjd", cast(p), cast(v)).reshape(n, heads, dim)
        if opts["gate"]:
            o = o * jax.nn.sigmoid(mm(u_blk, b["wg"]))[..., None]
        return mm(o.reshape(n, heads * dim), b["wo"])

    x = x + _blocks(attend, u, positions)
    return x + feed_forward(cfg, b, rms(x, b["ffn_norm"], eps), mm), (k, v)


def _tower(cfg, w, tokens, first_pos, state, opts):
    """``(x [T, D], [(k, v) a layer])`` of ``tokens`` [T] at positions
    ``first_pos ..`` after ``state``."""
    n = cfg["num_hidden_layers"]
    positions = first_pos + jnp.arange(tokens.shape[0])
    x = w["embed"][tokens].astype(jnp.float32)
    kept = []
    for i, b in enumerate(w["blocks"]):
        kind = cfg["layer_types"][:n][i]
        x, kv = layer(
            cfg, b, kind, cfg["num_attention_heads_per_layer"][:n][i], x,
            positions, None if state is None else state[i], opts,
        )
        kept.append(kv)
    return x, kept


def _options(cfg, cast, cache_cast, gate, window, full_rope):
    def mm(a, b):
        return cast(a.astype(jnp.float32)) @ cast(b.astype(jnp.float32))

    return {
        "mm": mm, "cast": cast, "cache_cast": cache_cast, "gate": gate,
        "window": cfg["sliding_window"] if window is None else window,
        "full_rope": full_rope,
    }


_JITTED = {}


def _jitted(cfg, what, control, build):
    key = (json.dumps(cfg, sort_keys=True), what, BLOCK) + tuple(
        sorted(control.items(), key=lambda kv: kv[0])
    )
    if key not in _JITTED:
        _JITTED[key] = jax.jit(build())
    return _JITTED[key]


def _padded(tokens, length):
    return jnp.zeros((length,), jnp.int32).at[: len(tokens)].set(
        jnp.asarray(tokens, jnp.int32)
    )


def prefix_state(cfg, w, prefix, *, cast=_identity, cache_cast=_identity,
                 gate=True, window=None, full_rope="yarn"):
    """The keys and values every layer keeps of ``prefix`` (tokens at
    positions 0 ..): ``[(k, v) a layer]``, a
    sliding layer's the last ``window - 1`` alone.  The same for every
    request that opens with the prefix."""
    control = dict(cast=cast, cache_cast=cache_cast, gate=gate, window=window,
                   full_rope=full_rope)
    opts = _options(cfg, **control)

    def build():
        def run(w_, tokens):
            _, kept = _tower(cfg, w_, tokens, 0, None, opts)
            n = cfg["num_hidden_layers"]
            return [
                (k, v) if kind == FULL
                else (k[-(opts["window"] - 1):], v[-(opts["window"] - 1):])
                for kind, (k, v) in zip(cfg["layer_types"][:n], kept)
            ]
        return run

    with jax.default_matmul_precision("highest"):
        return _jitted(cfg, ("state", len(prefix)), control, build)(
            w, jnp.asarray(prefix, jnp.int32)
        )


def logits(cfg, w, tokens, *, state=None, n_past=0, first_row=0, pad_to=None,
           rows_pad_to=None, cast=_identity, cache_cast=_identity, gate=True,
           window=None, full_rope="yarn"):
    """tokens [T] at positions ``n_past ..`` after ``state`` (:func:
    `prefix_state` of the ``n_past`` tokens before them; None: none) ->
    logits [T - first_row, vocabulary] float32 of the positions from
    ``first_row`` on.  The sequence is padded to ``pad_to`` and the rows
    returned are computed ``rows_pad_to`` at a time (both rounded up to
    multiples of ``BLOCK``; the masks keep the padding from the rows
    returned), so requests of many lengths share one compiled program."""
    t = len(tokens)
    n_rows = -(-max(t - first_row, rows_pad_to or 0) // BLOCK) * BLOCK
    padded_len = -(-max(t, pad_to or 0, first_row + n_rows) // BLOCK) * BLOCK
    control = dict(cast=cast, cache_cast=cache_cast, gate=gate, window=window,
                   full_rope=full_rope)
    opts = _options(cfg, **control)

    def build():
        def run(w_, tokens_, state_, first_row_):
            x, _ = _tower(cfg, w_, tokens_, n_past, state_, opts)
            # the head over the rows asked for alone
            x = jax.lax.dynamic_slice_in_dim(x, first_row_, n_rows, axis=0)
            return _blocks(
                lambda x_blk: opts["mm"](
                    rms(x_blk, w_["final_norm"], cfg["rms_norm_eps"]), w_["head"]
                ),
                x,
            )
        return run

    what = ("logits", padded_len, n_rows, n_past, state is None)
    with jax.default_matmul_precision("highest"):
        return _jitted(cfg, what, control, build)(
            w, _padded(tokens, padded_len), state, jnp.int32(first_row)
        )[: t - first_row]


def served_gaps(ref_logits, served):
    """For each served token, how far its reference logit lies below the
    reference's best at that position (0 where the served token IS the
    reference's greedy choice).  ``ref_logits`` are those of prompt +
    served[:-1] from the prompt's last position on."""
    served = jnp.asarray(served, jnp.int32)
    rows = ref_logits[: served.shape[0]]
    best = jnp.max(rows, axis=-1)
    got = jnp.take_along_axis(rows, served[:, None], axis=1)[:, 0]
    return best - got
