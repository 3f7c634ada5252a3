"""Plain reference of the ``keye-vl2-30b-a3b-l6`` configuration: the forward
pass over one sequence in ``jax.numpy``, float32 at ``highest`` matmul
precision, K and V of every position held per head, masks built from
positions and from the indexer's own float32 scores, no paged cache, no
kernels, no batching, a loop over the experts.  It follows
``configs/keye-vl2-30b-a3b-l6.json`` and imports nothing of the program.

The equations (``n(x; g) = x * rsqrt(mean(x^2) + eps) * g``, eps 1e-6;
``u = n(x; attn_norm)``), every layer alike:

- ``q = rot(n_head(u wq; q_norm))`` (32 heads x 128), ``k = rot(n_head(u
  wk; k_norm))`` (4 x 128), ``v = u wv`` (4 x 128); ``n_head`` is ``n``
  over one head's 128 values; ``rot`` turns the pairs ``(a[i], a[i +
  64])`` by the position times ``1e7 ** (-2i / 128)``.
- INDEXER: ``q_I = rot_I(u wq_idx)`` (16 heads x 64), ``k_I = rot_I(LN(u
  wk_idx; gain, bias))`` (64, one key head), ``rot_I`` the same turn over
  the indexer's whole head (pairs ``(a[i], a[i + 32])``, ``1e7 ** (-2i /
  64)``); ``w = u w_idx`` (16); ``I[t, s] = sum_j w[t, j] 16^-0.5 relu(q_I
  [t, j] . k_I[s] 64^-0.5)``; query ``t`` attends the ``topk`` = 2,048
  keys ``s <= t`` of largest ``I[t, s]`` (all of them while ``t < 2,048``;
  of equal scores the earlier key first).
- ``o[t, h] = softmax_s(q[t, h] . k[s, h // 8] 128^-0.5) v[s, h // 8]`` over
  those keys; ``x += concat(o) wo``.
- ``h = n(x; ffn_norm)``; ``p = softmax(h router)`` over 128; the 8
  largest, ``w = p_sel / sum(p_sel)``; ``x += sum_e w_e down_e(silu(gate_e
  h) * up_e h)``.
- after the last layer ``n(x; final_norm)`` and the head, at the rows
  asked for alone.

A served sequence is a SHARED PREFIX and a tail of its own.  Causality
makes every layer's keys, values and indexer keys at the prefix's
positions the same whatever follows, so the reference computes them ONCE a
seed (:func:`prefix_state`: a full forward pass over the prefix, every
position against every earlier one) and each request's tail against them
(:func:`forward` with ``state``): the same numbers as one pass over prefix
+ tail, at a hundredth of the work.

Controls, each of which a run's ``correct`` must catch: ``cast`` rounds
both inputs of every matrix product through a lower precision and back
(float8_e4m3fn, the step below the bfloat16 the configuration states);
``select`` replaces the selection by score: ``"all"`` attends every
earlier key, ``"recent"`` the last ``topk``; ``index_topk`` overrides how
many are kept; ``past_index="zero"`` reads zeros for the indexer's keys at
the prefix's positions, which is what a prefix cache that shares K/V and
not the indexer's keys would serve.

Beside the logits the forward returns, for the rows asked for, the share
of the selected keys that the same indexer keeps when ``q_I`` and ``k_I``
are rounded to bfloat16 as the program rounds them.

Sizes: projections, experts and the head run in row blocks of ``BLOCK``;
attention ``QBLOCK`` queries at a time, one K/V head's 8 query heads at a
time, against every key up to the end of the queries' eighth of the
sequence (a later key is visible to none of them), so that 66k positions
fit one chip beside the weights."""

from __future__ import annotations

import json

import jax
import jax.numpy as jnp

BLOCK = 512
QBLOCK = 128
KEY_STEPS = 8


def _identity(a):
    return a


def _bf16(a):
    return a.astype(jnp.bfloat16).astype(jnp.float32)


def rms(x, gain, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def layer_norm(x, gain, bias, eps):
    x = x.astype(jnp.float32)
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain + bias


def rot(a, positions, theta):
    """Rotate the pairs ``(a[..., i], a[..., i + half])`` of the last axis
    by ``positions`` (indexing the first axis) times ``theta ** (-2i /
    dim)``."""
    dim = a.shape[-1]
    half = dim // 2
    freqs = 1.0 / float(theta) ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    angle = positions.astype(jnp.float32)[:, None] * freqs[None, :]
    shape = (a.shape[0],) + (1,) * (a.ndim - 2) + (half,)
    cos, sin = jnp.cos(angle).reshape(shape), jnp.sin(angle).reshape(shape)
    lo, hi = a[..., :half], a[..., half:]
    return jnp.concatenate([lo * cos - hi * sin, lo * sin + hi * cos], axis=-1)


def _blocks(fn, *arrays, block=None):
    """``fn`` over row blocks of ``block`` (``BLOCK`` by default; the
    arrays' first axis is a multiple of it), results stacked back."""
    block = block or BLOCK
    n = arrays[0].shape[0] // block
    split = tuple(a.reshape((n, block) + a.shape[1:]) for a in arrays)
    out = jax.lax.map(lambda xs: fn(*xs), split)
    return jax.tree_util.tree_map(
        lambda o: o.reshape((n * block,) + o.shape[2:]), out
    )


def feed_forward(cfg, b, h, mm):
    """The routed feed-forward of one block on normalised rows ``h``."""
    p = jax.nn.softmax(mm(h, b["router"]), axis=-1)
    top, idx = jax.lax.top_k(p, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        top = top / jnp.sum(top, axis=-1, keepdims=True)

    def expert(y, e):
        w_e = jnp.sum(jnp.where(idx == e, top, 0.0), axis=-1)
        gate, up, down = (b[n][e] for n in ("experts_gate", "experts_up", "experts_down"))
        return y + w_e[:, None] * mm(jax.nn.silu(mm(h, gate)) * mm(h, up), down), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(h), jnp.arange(cfg["num_experts"]))
    return y


def selection(index_scores, key_pos, pos_blk, select, top_k):
    """[q, keys] bool: the keys each query attends."""
    causal = key_pos[None, :] <= pos_blk[:, None]
    if select == "all":
        return causal
    if select == "recent":
        return causal & (key_pos[None, :] > pos_blk[:, None] - top_k)
    scores = jnp.where(causal, index_scores, -jnp.inf)
    k = min(top_k, scores.shape[-1])
    least = jax.lax.top_k(scores, k)[0][:, -1:]
    above, ties = scores > least, scores == least
    room = k - jnp.sum(above, axis=-1, keepdims=True)
    return causal & (above | (ties & (jnp.cumsum(ties, axis=-1) <= room)))


def _rows(cfg, b, x_blk, pos_blk, mm):
    """What one block of positions gives a layer: ``q`` [n, H, D], ``k``,
    ``v`` [n, G, D], the indexer's ``q_i`` [n, J, d_i], ``k_i`` [n, d_i]
    and ``w_i`` [n, J] (its factors folded in)."""
    eps, sa = cfg["rms_norm_eps"], cfg["sa_config"]
    heads, groups, dim = (
        cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    )
    j, d_i = sa["indexer_num_heads"], sa["indexer_head_dim"]
    theta, n = cfg["rope_theta"], x_blk.shape[0]
    u = rms(x_blk, b["attn_norm"], eps)
    q = rot(rms(mm(u, b["wq"]).reshape(n, heads, dim), b["q_norm"], eps), pos_blk, theta)
    k = rot(rms(mm(u, b["wk"]).reshape(n, groups, dim), b["k_norm"], eps), pos_blk, theta)
    v = mm(u, b["wv"]).reshape(n, groups, dim)
    q_i = rot(mm(u, b["wq_idx"]).reshape(n, j, d_i), pos_blk, theta)
    k_i = rot(
        layer_norm(mm(u, b["wk_idx"]), b["k_idx_gain"], b["k_idx_bias"], eps),
        pos_blk, theta,
    )
    w_i = mm(u, b["w_idx"]) * j ** -0.5 * d_i ** -0.5
    return q, k, v, q_i, k_i, w_i


def attention(cfg, b, x, positions, length, past, mm, cast, select, top_k,
              past_index, with_share):
    """One layer's attention update over x [T, D] (T a multiple of BLOCK,
    the first ``length`` rows real) at ``positions``, after the ``past``
    positions' ``{"k", "v", "k_i"}`` (None: there are none): ``(update [T,
    D], shared [T], own {"k", "v", "k_i"})``."""
    heads, groups, dim = (
        cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    )
    per = heads // groups
    t = x.shape[0]
    q, k, v, q_i, k_i, w_i = _blocks(
        lambda x_blk, pos_blk: _rows(cfg, b, x_blk, pos_blk, mm), x, positions
    )
    own = {"k": k, "v": v, "k_i": k_i}
    n_past = 0
    if past is not None:
        n_past = past["k"].shape[0]
        seen_k_i = jnp.zeros_like(past["k_i"]) if past_index == "zero" else past["k_i"]
        k = jnp.concatenate([past["k"], k])
        v = jnp.concatenate([past["v"], v])
        k_i = jnp.concatenate([seen_k_i, k_i])
    key_pos = jnp.concatenate([jnp.arange(n_past), positions])
    steps = KEY_STEPS if t >= 4 * KEY_STEPS * QBLOCK else 1
    edges = [
        n_past + -(-(t * (i + 1)) // (steps * QBLOCK)) * QBLOCK
        for i in range(steps)
    ]

    def attend_blk(q_blk, q_i_blk, w_i_blk, pos_blk, first):
        def over(n_keys):
            def live(_):
                def see(rounding):
                    sc = jnp.einsum(
                        "qjd,kd->qjk", rounding(cast(q_i_blk)),
                        rounding(cast(k_i[:n_keys])),
                    )
                    scores = jnp.einsum("qjk,qj->qk", jax.nn.relu(sc), w_i_blk)
                    return selection(scores, key_pos[:n_keys], pos_blk, select, top_k)

                chosen = see(_identity)
                share = jnp.zeros((QBLOCK,), jnp.float32)
                if with_share:
                    both = jnp.sum(chosen & see(_bf16), axis=-1)
                    share = both / jnp.maximum(jnp.sum(chosen, axis=-1), 1)
                outs = []
                for g in range(groups):
                    sc = jnp.einsum(
                        "qrd,kd->rqk",
                        cast(q_blk[:, g * per:(g + 1) * per]),
                        cast(k[:n_keys, g]),
                    ) * dim ** -0.5
                    p = jax.nn.softmax(jnp.where(chosen[None], sc, -jnp.inf), axis=-1)
                    outs.append(jnp.einsum("rqk,kd->qrd", cast(p), cast(v[:n_keys, g])))
                return jnp.concatenate(outs, axis=1).reshape(QBLOCK, -1), share

            return live

        def padding(_):
            return (
                jnp.zeros((QBLOCK, heads * dim), jnp.float32),
                jnp.zeros((QBLOCK,), jnp.float32),
            )

        # blocks wholly past the sequence are padding: skipped
        step = jnp.searchsorted(jnp.asarray(edges), n_past + first + QBLOCK)
        which = jnp.where(first < length, jnp.minimum(step, steps - 1), steps)
        return jax.lax.switch(which, [over(n) for n in edges] + [padding], None)

    firsts = jnp.arange(0, t, QBLOCK)
    o, shared = jax.lax.map(
        lambda xs: attend_blk(*xs),
        (
            q.reshape((-1, QBLOCK) + q.shape[1:]),
            q_i.reshape((-1, QBLOCK) + q_i.shape[1:]),
            w_i.reshape(-1, QBLOCK, w_i.shape[-1]),
            positions.reshape(-1, QBLOCK), firsts,
        ),
    )
    o = o.reshape(t, heads * dim)
    return _blocks(lambda o_blk: mm(o_blk, b["wo"]), o), shared.reshape(t), own


def _tower(cfg, w, tokens, positions, length, state, cast, select, top_k,
           past_index, with_share=True):
    """``(x [T, D], shared [T], state)`` of tokens [T] at ``positions``
    after ``state`` (a layer's ``{"k", "v", "k_i"}`` each, or None)."""
    eps = cfg["rms_norm_eps"]

    def mm(a, b):
        return cast(a.astype(jnp.float32)) @ cast(b.astype(jnp.float32))

    x = w["embed"][tokens].astype(jnp.float32)
    shared, new_state = [], []
    for layer, b in enumerate(w["blocks"]):
        update, share, own = attention(
            cfg, b, x, positions, length, None if state is None else state[layer],
            mm, cast, select, top_k, past_index, with_share,
        )
        x = x + update
        x = x + _blocks(
            lambda x_blk: feed_forward(cfg, b, rms(x_blk, b["ffn_norm"], eps), mm), x
        )
        shared.append(share)
        new_state.append(own)
    return x, jnp.mean(jnp.stack(shared), axis=0), new_state


_JITTED = {}


def _key(cfg, *rest):
    return (json.dumps(cfg, sort_keys=True),) + rest


def prefix_state(cfg, w, tokens, *, cast=_identity, select="score",
                 index_topk=None):
    """Every layer's ``{"k", "v", "k_i"}`` at the positions of the shared
    prefix ``tokens`` [P], computed once a seed and handed to
    :func:`forward` as ``state``."""
    p = len(tokens)
    padded_len = -(-p // BLOCK) * BLOCK
    top_k = int(index_topk or cfg["sa_config"]["topk"])
    key = _key(cfg, "prefix", cast, select, top_k, p)
    if key not in _JITTED:
        def run(w_, t_):
            state = _tower(
                cfg, w_, t_, jnp.arange(padded_len), jnp.int32(p), None, cast,
                select, top_k, "as_cached", with_share=False,
            )[2]
            return jax.tree_util.tree_map(lambda a: a[:p], state)

        _JITTED[key] = jax.jit(run)
    padded = jnp.zeros((padded_len,), jnp.int32).at[:p].set(
        jnp.asarray(tokens, jnp.int32)
    )
    with jax.default_matmul_precision("highest"):
        return _JITTED[key](w, padded)


def forward(cfg, w, tokens, *, state=None, cast=_identity, select="score",
            index_topk=None, past_index="as_cached", pad_to=None, first_row=0,
            rows_pad_to=None):
    """tokens [T] (what follows ``state``'s positions; the whole sequence
    without one) -> ``(logits [T - first_row, vocabulary], shared [T -
    first_row])`` float32 of the rows from ``first_row`` on.  The tokens
    are padded to ``pad_to`` and the rows returned are computed
    ``rows_pad_to`` at a time (both rounded up to multiples of ``BLOCK``;
    the masks keep the padding from the rows returned), so requests of
    many lengths share one compiled program."""
    t = len(tokens)
    top_k = int(index_topk or cfg["sa_config"]["topk"])
    n_rows = -(-max(t - first_row, rows_pad_to or 0) // BLOCK) * BLOCK
    padded_len = -(-max(t, pad_to or 0, first_row + n_rows) // BLOCK) * BLOCK
    n_past = 0 if state is None else state[0]["k"].shape[0]
    key = _key(cfg, "tail", cast, select, top_k, past_index, n_past, n_rows)
    if key not in _JITTED:
        eps = cfg["rms_norm_eps"]

        def run(w_, t_, n_, r_, state_):
            x, shared, _ = _tower(
                cfg, w_, t_, n_past + jnp.arange(t_.shape[0]), n_, state_,
                cast, select, top_k, past_index,
            )
            x = jax.lax.dynamic_slice_in_dim(x, r_, n_rows, axis=0)

            def head(x_blk):
                h = rms(x_blk, w_["final_norm"], eps)
                return cast(h) @ cast(w_["head"].astype(jnp.float32))

            return _blocks(head, x), jax.lax.dynamic_slice_in_dim(shared, r_, n_rows)

        _JITTED[key] = jax.jit(run)
    padded = jnp.zeros((padded_len,), jnp.int32).at[:t].set(
        jnp.asarray(tokens, jnp.int32)
    )
    with jax.default_matmul_precision("highest"):
        logits, shared = _JITTED[key](
            w, padded, jnp.int32(t), jnp.int32(first_row), state
        )
    return logits[: t - first_row], shared[: t - first_row]


def logits(cfg, w, tokens, **kwargs):
    return forward(cfg, w, tokens, **kwargs)[0]


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
