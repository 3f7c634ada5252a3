"""Plain reference of the ``smallthinker-21b-l8`` configuration: the full
forward pass over one sequence in ``jax.numpy``, float32 at ``highest``
matmul precision, no cache, no kernels, no batching, masks built from
positions, a loop over the experts.  It follows ``configs/
smallthinker-21b-l8.json`` and imports nothing of the program.

The equations (``rms(x; g) = x * rsqrt(mean(x^2) + eps) * g``), layer ``l``
a WINDOW layer where ``sliding_window_layout[l]`` is 1 (``rope_layout[l]``
says the same) and a GLOBAL layer otherwise:

- ``a = rms(x; attn_norm)``; the router reads ``a``, the layer's input
  BEFORE attention: ``r = a router`` over the 64 experts, the 6 largest,
  ``w = softmax`` over those 6 logits;
- ``q = a wq`` (28 heads of 128), ``k = a wk``, ``v = a wv`` (4 heads of
  128), no biases, no q/k norm.  WINDOW: q and k are turned by the position
  times ``theta ** (-2i / 128)`` over the whole head, the pairs ``(a[i],
  a[i + 64])``; key ``j`` is visible to query ``i`` iff ``i - W < j <= i``
  (the last ``W`` = 4096 keys, the query's own among them).  GLOBAL: nothing
  is turned; key ``j`` is visible iff ``j <= i``;
- ``o = softmax(q k^T / sqrt(128)) v``, query head ``h`` reading K/V head
  ``h // 7``; ``x1 = x + o wo``;
- ``b = rms(x1; ffn_norm)``; ``y = sum_e w_e (relu(b gate_e) * (b up_e))
  down_e`` over the 6 chosen; ``x2 = x1 + y``; no shared expert;
- after the last layer ``rms(x; final_norm)`` and the untied head.

Departures from the published description, each listed under ``assumed``
in the configuration file: ``described_as`` speaks of "primary+secondary
experts" and the config has primary experts only, so nothing secondary is
built; the router reads the NORMALISED input ``a`` (not the raw ``x``);
the rotary pairs are split by halves; the window counts the query's own
position.

``cast`` rounds both inputs of every matrix product through a lower
precision and back (identity for the reference; the first control passes
float8_e4m3fn, the step below the bfloat16 the configuration states).
``cache_cast`` rounds what a cache would hold, the turned ``k`` and ``v``,
before attention reads them (the second control: a K/V cache in float8).

A 16k-token sequence is computed in row blocks of ``BLOCK``: projections
and experts a block at a time, attention a block of queries at a time
against every key (K and V of a layer are materialised once)."""

from __future__ import annotations

import json

import jax
import jax.numpy as jnp

BLOCK = 512


def _identity(a):
    return a


def rms(x, gain, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def inv_freq(cfg):
    """[head_dim / 2] float32: ``rope_theta ** (-2i / head_dim)``."""
    dim = cfg["head_dim"]
    return 1.0 / float(cfg["rope_theta"]) ** (
        jnp.arange(0, dim, 2, dtype=jnp.float32) / dim
    )


def rot(a, positions, freqs):
    """Rotate the pairs ``(a[..., i], a[..., i + half])`` of the last axis;
    ``positions`` indexes the first axis of ``a`` [T, heads, dim]."""
    half = a.shape[-1] // 2
    angle = positions.astype(jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    lo, hi = a[..., :half], a[..., half:]
    return jnp.concatenate([lo * cos - hi * sin, lo * sin + hi * cos], axis=-1)


def _blocks(fn, *arrays):
    """``fn`` over row blocks of ``BLOCK`` (the arrays' first axis is a
    multiple of it), results stacked back (a tuple of results each)."""
    n = arrays[0].shape[0] // BLOCK
    split = tuple(a.reshape((n, BLOCK) + a.shape[1:]) for a in arrays)
    out = jax.lax.map(lambda xs: fn(*xs), split)
    return jax.tree_util.tree_map(
        lambda o: o.reshape((n * BLOCK,) + o.shape[2:]), out
    )


def route(cfg, logits):
    """[T, E] router logits -> (chosen experts [T, k], their weights)."""
    top, idx = jax.lax.top_k(logits, cfg["moe_num_active_primary_experts"])
    if cfg["norm_topk_prob"]:
        return idx, jax.nn.softmax(top, axis=-1)
    share = jnp.exp(top - jax.nn.logsumexp(logits, axis=-1, keepdims=True))
    return idx, share


def experts(b, h, idx, weight, mm):
    """``sum_e w_e (relu(h gate_e) * (h up_e)) down_e`` on normalised rows
    ``h`` [T, D], every expert over every row, weighted 0 where it was not
    chosen."""
    y = jnp.zeros_like(h)
    for e in range(b["experts_gate"].shape[0]):
        w_e = jnp.sum(jnp.where(idx == e, weight, 0.0), axis=-1)
        act = jax.nn.relu(mm(h, b["experts_gate"][e])) * mm(h, b["experts_up"][e])
        y = y + w_e[:, None] * mm(act, b["experts_down"][e])
    return y


def visible(windowed, window, key_pos, query_pos):
    """[queries, keys] bool: which keys a query may read."""
    see = key_pos[None, :] <= query_pos[:, None]
    if windowed:
        see = see & (key_pos[None, :] > query_pos[:, None] - window)
    return see


def layer(cfg, b, windowed, x, positions, mm, cast, cache_cast, freqs):
    """One layer over x [T, D] (T a multiple of BLOCK)."""
    eps = cfg["rms_norm_eps"]
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dim, window = cfg["head_dim"], cfg["sliding_window_size"]
    t = x.shape[0]

    def keys_values(x_blk, pos_blk):
        a = rms(x_blk, b["attn_norm"], eps)
        k = mm(a, b["wk"]).reshape(BLOCK, kv_heads, dim)
        v = mm(a, b["wv"]).reshape(BLOCK, kv_heads, dim)
        if windowed:
            k = rot(k, pos_blk, freqs)
        return cache_cast(k), cache_cast(v)

    k, v = _blocks(keys_values, x, positions)  # [T, kv_heads, dim] each
    key_pos = jnp.arange(t)

    def attend_and_route(x_blk, pos_blk):
        a = rms(x_blk, b["attn_norm"], eps)
        idx, weight = route(cfg, mm(a, b["router"]))
        q = mm(a, b["wq"]).reshape(BLOCK, heads, dim)
        if windowed:
            q = rot(q, pos_blk, freqs)
        q = q.reshape(BLOCK, kv_heads, heads // kv_heads, dim)
        s = jnp.einsum("qgjd,kgd->gjqk", cast(q), cast(k)) * dim ** -0.5
        see = visible(windowed, window, key_pos, pos_blk)
        p = jax.nn.softmax(jnp.where(see[None, None], s, -jnp.inf), axis=-1)
        o = jnp.einsum("gjqk,kgd->qgjd", cast(p), cast(v)).reshape(BLOCK, -1)
        x1 = x_blk + mm(o, b["wo"])
        return x1 + experts(b, rms(x1, b["ffn_norm"], eps), idx, weight, mm)

    return _blocks(attend_and_route, x, positions)


def _forward(cfg, w, tokens, first_row, n_rows, cast, cache_cast):
    n = cfg["num_hidden_layers"]
    windowed = [bool(s) for s in cfg["sliding_window_layout"][:n]]
    assert windowed == [bool(r) for r in cfg["rope_layout"][:n]]
    freqs = inv_freq(cfg)
    positions = jnp.arange(tokens.shape[0])

    def mm(a, b):
        return cast(a.astype(jnp.float32)) @ cast(b.astype(jnp.float32))

    x = w["embed"][tokens].astype(jnp.float32)
    for b, is_window in zip(w["blocks"], windowed):
        x = layer(cfg, b, is_window, x, positions, mm, cast, cache_cast, freqs)
    # the head over the rows asked for alone: 16k rows of a 152k-wide
    # vocabulary in float32 would be 10 GB
    x = jax.lax.dynamic_slice_in_dim(x, first_row, n_rows, axis=0)
    return _blocks(
        lambda x_blk: mm(rms(x_blk, w["final_norm"], cfg["rms_norm_eps"]), w["head"]),
        x,
    )


_JITTED = {}


def logits(cfg, w, tokens, *, cast=_identity, cache_cast=_identity,
           pad_to=None, first_row=0, rows_pad_to=None):
    """tokens [T] -> logits [T - first_row, vocabulary] float32 of the
    positions from ``first_row`` on.  The sequence is padded to ``pad_to``
    and the rows returned are computed ``rows_pad_to`` at a time (both
    rounded up to multiples of ``BLOCK``; the masks keep the padding from
    the rows returned), so requests of many lengths can share one compiled
    program."""
    t = len(tokens)
    n_rows = -(-max(t - first_row, rows_pad_to or 0) // BLOCK) * BLOCK
    padded_len = -(-max(t, pad_to or 0, first_row + n_rows) // BLOCK) * BLOCK
    key = (json.dumps(cfg, sort_keys=True), cast, cache_cast, BLOCK, n_rows)
    if key not in _JITTED:
        _JITTED[key] = jax.jit(
            lambda w_, t_, r_: _forward(cfg, w_, t_, r_, n_rows, cast, cache_cast)
        )
    padded = jnp.zeros((padded_len,), jnp.int32).at[:t].set(
        jnp.asarray(tokens, jnp.int32)
    )
    with jax.default_matmul_precision("highest"):
        return _JITTED[key](w, padded, jnp.int32(first_row))[:t - first_row]


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
