"""Plain reference of the ``dots3-ep16-l5`` configuration: the full forward
pass over one sequence in ``jax.numpy``, float32 at ``highest`` matmul
precision, K and V materialised per head, masks built from positions and
from the indexer's own float32 scores, no cache, no kernels, no batching, a
loop over the experts held.  It follows ``configs/dots3-ep16-l5.json`` and
imports nothing of the program.

The equations (``n(x; g) = x * rsqrt(mean(x^2) + eps) * g``, eps 1e-5;
``u = n(x; attn_norm)``; layer ``l`` FULL where ``layer_types[l]`` is
``"full_attention"`` and a WINDOW layer otherwise):

- FULL: ``c_q = r_q n(u wq_a; q_norm)`` (1,024), ``r_q = (5120 / 1024)
  ^ 0.5``; per head (128) ``q = [c_q wq_b_nope (128), rot(c_q wq_b_rope)
  (64)]``; ``[c_kv (512), k_r (64)] = u wkv_a``; ``c = r_kv n(c_kv;
  kv_norm)``, ``r_kv = (5120 / 512) ^ 0.5``; per head ``k = [c wk_b,
  rot(k_r)]``, ``v = c wv_b`` (128); ``score = q . k * 192^-0.5``; rotary
  base 8e7.  INDEXER: ``q_I = c_q wq_idx`` (64 heads x 128), ``k_I =
  LN(u wk_idx; gain, bias)`` (128), both turned on their first 64 values;
  ``w = u w_idx`` (64); ``I[t, s] = sum_j w[t, j] 64^-0.5 relu(q_I[t, j] .
  k_I[s] 128^-0.5)``; query ``t`` attends the ``index_topk`` = 2,048 keys
  ``s <= t`` of largest ``I[t, s]`` (all of them while ``t < 2,048``;
  of equal scores the earlier key first).
- WINDOW: the same latent attention at its own sizes (``swa_`` keys: 64
  heads x (192 + 64), latent 1,024, ``r_q = r_kv = 5 ^ 0.5``, scale
  ``256^-0.5``, rotary base 5e4), no indexer; key ``s`` is visible iff ``t
  - 513 < s <= t`` (513 keys, the query's own among them).
- both: ``g = sigmoid(u wg)``, one a head; ``o_h <- g_h o_h``; ``x +=
  concat(o) wo``.
- routed layer, ``h = n(x; ffn_norm)``: ``s = sigmoid(h router)`` over all
  256; the 8 largest of ``s + router_bias`` (``noaux_tc``); ``w = s_sel /
  sum(s_sel)`` from the UNBIASED scores, times ``routed_scaling_factor``;
  the part of ``sum_e w_e down_e(silu(gate_e h) * up_e h)`` that the
  experts ``[first, first + held)`` give, plus the shared expert.  Layer
  0's feed-forward: the same gated form, width 13,824, no router.
- after the last layer ``n(x; final_norm)`` and the head over the
  vocabulary slice, at the rows asked for alone.

``rot`` turns the pairs ``(a[i], a[i + 32])`` by the position times
``theta ** (-2i / 64)`` (``assumed.rotary_pairs``).

Controls, each of which a run's ``correct`` must catch: ``cast`` rounds
both inputs of every matrix product through a lower precision and back
(float8_e4m3fn, the step below the bfloat16 the configuration states);
``select`` replaces the selection by score: ``"all"`` attends every
earlier key, ``"recent"`` the last ``index_topk``; ``index_topk``
overrides how many are kept.

Beside the logits the forward returns, for the rows asked for, the share
of the selected keys that the same indexer keeps when ``q_I`` and ``k_I``
are rounded to bfloat16 as the program rounds them: how far two honest
selections may differ at the 2,048th score's rounding.

A 33k-token sequence is computed in row blocks of ``BLOCK`` (projections,
experts, window attention: a block of queries against its own and the
block of keys before it) and, in a full layer, ``QBLOCK`` queries at a
time against every key up to the end of their eighth of the sequence,
``HEAD_GROUP`` heads at a time (K and V of a group are materialised once:
all 128 heads at 33k keys would be 4.4 GB)."""

from __future__ import annotations

import json

import jax
import jax.numpy as jnp

BLOCK = 512
QBLOCK = 64
HEAD_GROUP = 32
KEY_STEPS = 8


def _identity(a):
    return a


def rms(x, gain, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def layer_norm(x, gain, bias, eps):
    x = x.astype(jnp.float32)
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain + bias


def inv_freq(dim, theta):
    return 1.0 / float(theta) ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)


def rot(a, positions, freqs):
    """Rotate the pairs ``(a[..., i], a[..., i + half])`` of the last axis;
    ``positions`` indexes the first axis of ``a``."""
    half = a.shape[-1] // 2
    angle = positions.astype(jnp.float32)[:, None] * freqs[None, :]
    shape = (a.shape[0],) + (1,) * (a.ndim - 2) + (half,)
    cos, sin = jnp.cos(angle).reshape(shape), jnp.sin(angle).reshape(shape)
    lo, hi = a[..., :half], a[..., half:]
    return jnp.concatenate([lo * cos - hi * sin, lo * sin + hi * cos], axis=-1)


def rot_leading(a, positions, freqs):
    """``rot`` of the first ``2 * len(freqs)`` values of the last axis."""
    n = 2 * freqs.shape[0]
    return jnp.concatenate([rot(a[..., :n], positions, freqs), a[..., n:]], axis=-1)


def _blocks(fn, *arrays, block=None):
    """``fn`` over row blocks of ``block`` (``BLOCK`` by default; the
    arrays' first axis is a multiple of it), results stacked back (a tuple
    of results each)."""
    block = block or BLOCK
    n = arrays[0].shape[0] // block
    split = tuple(a.reshape((n, block) + a.shape[1:]) for a in arrays)
    out = jax.lax.map(lambda xs: fn(*xs), split)
    return jax.tree_util.tree_map(
        lambda o: o.reshape((n * block,) + o.shape[2:]), out
    )


def sizes_of(cfg, full: bool) -> dict:
    p = "" if full else "swa_"
    hidden = cfg["hidden_size"]
    rescale = bool(cfg["apply_mla_qkv_lora_rescale"])
    rank_q, rank_kv = cfg[p + "q_lora_rank"], cfg[p + "kv_lora_rank"]
    d_n, d_r = cfg[p + "qk_nope_head_dim"], cfg[p + "qk_rope_head_dim"]
    return {
        "heads": cfg[p + "num_attention_heads"], "d_c": rank_kv, "d_n": d_n,
        "d_r": d_r, "scale": (d_n + d_r) ** -0.5,
        "theta": cfg["rope_theta" if full else "swa_rope_theta"],
        "r_q": (hidden / rank_q) ** 0.5 if rescale else 1.0,
        "r_kv": (hidden / rank_kv) ** 0.5 if rescale else 1.0,
    }


def route(cfg, scores, bias):
    """[T, E] sigmoid scores -> (chosen experts [T, k], their weights):
    chosen by ``scores + bias``, weighted by the unbiased ``scores``."""
    _, idx = jax.lax.top_k(scores + bias, cfg["num_experts_per_tok"])
    top = jnp.take_along_axis(scores, idx, axis=-1)
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
    idx, weight = route(cfg, scores, b["router_bias"])
    y = gated(mm, h, b["shared_gate"], b["shared_up"], b["shared_down"])
    for e in range(b["experts_gate"].shape[0]):
        w_e = jnp.sum(jnp.where(idx == first_expert + e, weight, 0.0), axis=-1)
        y = y + w_e[:, None] * gated(
            mm, h, b["experts_gate"][e], b["experts_up"][e], b["experts_down"][e]
        )
    return y


def _queries(cfg, b, s, x_blk, pos_blk, mm, freqs):
    """``(u, c_q, q_nope [q, H, d_n], q_rope [q, H, d_r])`` of a block."""
    eps = cfg["rms_norm_eps"]
    u = rms(x_blk, b["attn_norm"], eps)
    c_q = s["r_q"] * rms(mm(u, b["wq_a"]), b["q_norm"], eps)
    n = x_blk.shape[0]
    q_nope = mm(c_q, b["wq_b_nope"]).reshape(n, s["heads"], s["d_n"])
    q_rope = rot(
        mm(c_q, b["wq_b_rope"]).reshape(n, s["heads"], s["d_r"]), pos_blk, freqs
    )
    return u, c_q, q_nope, q_rope


def _latent_rows(cfg, b, s, x_blk, pos_blk, mm, freqs):
    """``(c [q, d_c], rot(k_r) [q, d_r], u)`` of a block: what a cache
    would hold of it."""
    eps = cfg["rms_norm_eps"]
    u = rms(x_blk, b["attn_norm"], eps)
    kv = mm(u, b["wkv_a"])
    c = s["r_kv"] * rms(kv[:, : s["d_c"]], b["kv_norm"], eps)
    return c, rot(kv[:, s["d_c"]:], pos_blk, freqs), u


def _attend(s, q_nope, q_rope, k_nope, k_r, v, see, cast, heads):
    """Heads ``heads`` (a slice) of the queries against materialised keys
    and values of those heads: [q, len(heads) * d_v]."""
    sc = jnp.einsum("qhd,khd->hqk", cast(q_nope[:, heads]), cast(k_nope))
    sc = sc + jnp.einsum("qhd,kd->hqk", cast(q_rope[:, heads]), cast(k_r))
    p = jax.nn.softmax(jnp.where(see[None], sc * s["scale"], -jnp.inf), axis=-1)
    return jnp.einsum("hqk,khd->qhd", cast(p), cast(v))


def selection(index_scores, key_pos, pos_blk, select, top_k):
    """[q, keys] bool: the keys each query attends in a full layer."""
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


def _bf16(a):
    return a.astype(jnp.bfloat16).astype(jnp.float32)


def index_keys(cfg, b, u, pos_blk, mm, freqs):
    """The indexer's key of each row of ``u`` [q, D]: [q, index_head_dim]."""
    k_i = layer_norm(
        mm(u, b["wk_idx"]), b["k_idx_gain"], b["k_idx_bias"], cfg["rms_norm_eps"]
    )
    return rot_leading(k_i, pos_blk, freqs)


def index_scores(cfg, b, u, c_q, pos_blk, k_i, mm, cast, freqs,
                 rounding=_identity):
    """``I[t, s]`` of a block of queries against every key: [q, keys]."""
    j, d_i = cfg["index_n_heads"], cfg["index_head_dim"]
    q_i = rot_leading(mm(c_q, b["wq_idx"]).reshape(-1, j, d_i), pos_blk, freqs)
    sc = jnp.einsum("qjd,kd->qjk", rounding(cast(q_i)), rounding(cast(k_i)))
    return jnp.einsum(
        "qjk,qj->qk", jax.nn.relu(sc * d_i ** -0.5), mm(u, b["w_idx"]) * j ** -0.5
    )


def full_attention(cfg, b, x, positions, length, mm, cast, select, top_k):
    """A full layer's attention update over x [T, D] (T a multiple of
    BLOCK) of which the first ``length`` rows are the sequence: ``(update
    [T, D], shared [T])``; ``shared`` is the share of a row's selected keys
    that a bfloat16 indexer selects too."""
    s, eps = sizes_of(cfg, True), cfg["rms_norm_eps"]
    freqs = inv_freq(s["d_r"], s["theta"])
    t, heads = x.shape[0], s["heads"]
    key_pos = jnp.arange(t)

    def rows(x_blk, pos_blk):
        c, k_r, u = _latent_rows(cfg, b, s, x_blk, pos_blk, mm, freqs)
        return c, k_r, index_keys(cfg, b, u, pos_blk, mm, freqs)

    c, k_r, k_i = _blocks(rows, x, positions)

    # a block of queries reads the keys up to the end of its eighth of the
    # sequence's padded length, not all of them: the same numbers (a later
    # key is visible to none of the block's queries) at half the work
    edges = [
        -(-(t * (i + 1)) // (KEY_STEPS * QBLOCK)) * QBLOCK
        for i in range(KEY_STEPS)
    ]

    def select_blk(x_blk, pos_blk, n_keys, with_share):
        u, c_q, _, _ = _queries(cfg, b, s, x_blk, pos_blk, mm, freqs)

        def see(rounding):
            return selection(
                index_scores(
                    cfg, b, u, c_q, pos_blk, k_i[:n_keys], mm, cast, freqs, rounding
                ),
                key_pos[:n_keys], pos_blk, select, top_k,
            )

        chosen = see(_identity)
        if not with_share:
            return chosen, jnp.zeros((x_blk.shape[0],), jnp.float32)
        both = jnp.sum(chosen & see(_bf16), axis=-1)
        return chosen, both / jnp.sum(chosen, axis=-1)

    outs = []
    shared = None
    for first in range(0, heads, HEAD_GROUP):
        group = slice(first, first + HEAD_GROUP)
        n_g = min(HEAD_GROUP, heads - first)
        wk = b["wk_b"].reshape(s["d_c"], heads, s["d_n"])[:, group].reshape(s["d_c"], -1)
        wv = b["wv_b"].reshape(s["d_c"], heads, -1)[:, group].reshape(s["d_c"], -1)
        k_nope = _blocks(lambda c_blk: mm(c_blk, wk), c).reshape(t, n_g, s["d_n"])
        v = _blocks(lambda c_blk: mm(c_blk, wv), c).reshape(t, n_g, -1)

        def attend_blk(x_blk, pos_blk):
            def over(n_keys):
                def live(_):
                    _, _, q_nope, q_rope = _queries(
                        cfg, b, s, x_blk, pos_blk, mm, freqs
                    )
                    # the selection is recomputed a group of heads (storing
                    # it would be [T, T]); the bfloat16 twin only once
                    see, share = select_blk(x_blk, pos_blk, n_keys, first == 0)
                    o = _attend(
                        s, q_nope, q_rope, k_nope[:n_keys], k_r[:n_keys],
                        v[:n_keys], see, cast, group,
                    )
                    return o.reshape(x_blk.shape[0], -1), share

                return live

            def padding(_):
                return (
                    jnp.zeros((x_blk.shape[0], n_g * v.shape[-1]), jnp.float32),
                    jnp.zeros((x_blk.shape[0],), jnp.float32),
                )

            # blocks wholly past the sequence are padding: skipped
            step = jnp.searchsorted(jnp.asarray(edges), pos_blk[-1] + 1)
            which = jnp.where(pos_blk[0] < length, step, KEY_STEPS)
            return jax.lax.switch(
                which, [over(n) for n in edges] + [padding], None
            )

        o_g, share = _blocks(attend_blk, x, positions, block=QBLOCK)
        if first == 0:
            shared = share
        outs.append(o_g.reshape(t, n_g, -1))
    o = jnp.concatenate(outs, axis=1)  # [T, H, d_v]

    def out_blk(x_blk, o_blk):
        u = rms(x_blk, b["attn_norm"], eps)
        gate = jax.nn.sigmoid(mm(u, b["wg"]))
        return mm((o_blk * gate[..., None]).reshape(x_blk.shape[0], -1), b["wo"])

    return _blocks(out_blk, x, o), shared


def window_attention(cfg, b, x, positions, mm, cast):
    """A window layer's attention update over x [T, D]: a block of
    queries against its own block of keys and the one before it."""
    s, eps = sizes_of(cfg, False), cfg["rms_norm_eps"]
    window = cfg["sliding_window_size"]
    assert window <= BLOCK + 1
    freqs = inv_freq(s["d_r"], s["theta"])
    t, heads = x.shape[0], s["heads"]

    def rows(x_blk, pos_blk):
        c, k_r, _ = _latent_rows(cfg, b, s, x_blk, pos_blk, mm, freqs)
        return (
            mm(c, b["wk_b"]).reshape(BLOCK, heads, s["d_n"]), k_r,
            mm(c, b["wv_b"]).reshape(BLOCK, heads, -1),
        )

    # one block of padding ahead, so that block i's keys are blocks i, i + 1
    k_nope, k_r, v = (
        jnp.pad(a, ((BLOCK, 0),) + ((0, 0),) * (a.ndim - 1))
        for a in _blocks(rows, x, positions)
    )

    def attend_blk(x_blk, pos_blk):
        u, _, q_nope, q_rope = _queries(cfg, b, s, x_blk, pos_blk, mm, freqs)
        first = pos_blk[0]  # the padded arrays' index of key ``first - BLOCK``

        def keys(a):
            return jax.lax.dynamic_slice_in_dim(a, first, 2 * BLOCK, axis=0)

        key_pos = first - BLOCK + jnp.arange(2 * BLOCK)
        see = (
            (key_pos[None, :] <= pos_blk[:, None]) & (key_pos[None, :] >= 0)
            & (key_pos[None, :] > pos_blk[:, None] - window)
        )
        o = _attend(
            s, q_nope, q_rope, keys(k_nope), keys(k_r), keys(v), see, cast,
            slice(None),
        )
        gate = jax.nn.sigmoid(mm(u, b["wg"]))
        return mm((o * gate[..., None]).reshape(BLOCK, -1), b["wo"])

    return _blocks(attend_blk, x, positions)


def _forward(cfg, w, tokens, length, first_row, n_rows, cast, select, top_k):
    eps = cfg["rms_norm_eps"]
    first_expert = cfg["deployment"]["first_expert"]
    kinds = cfg["layer_types"][: cfg["num_hidden_layers"]]
    positions = jnp.arange(tokens.shape[0])

    def mm(a, b):
        return cast(a.astype(jnp.float32)) @ cast(b.astype(jnp.float32))

    x = w["embed"][tokens].astype(jnp.float32)
    shared = []
    for b, kind in zip(w["blocks"], kinds):
        if kind == "full_attention":
            update, share = full_attention(
                cfg, b, x, positions, length, mm, cast, select, top_k
            )
            shared.append(jax.lax.dynamic_slice_in_dim(share, first_row, n_rows))
        else:
            update = window_attention(cfg, b, x, positions, mm, cast)
        x = x + update
        x = x + _blocks(
            lambda x_blk: feed_forward(
                cfg, b, rms(x_blk, b["ffn_norm"], eps), mm, first_expert
            ),
            x,
        )
    x = jax.lax.dynamic_slice_in_dim(x, first_row, n_rows, axis=0)
    logits = _blocks(lambda x_blk: mm(rms(x_blk, w["final_norm"], eps), w["head"]), x)
    return logits, jnp.mean(jnp.stack(shared), axis=0)


_JITTED = {}


def forward(cfg, w, tokens, *, cast=_identity, select="score", index_topk=None,
            pad_to=None, first_row=0, rows_pad_to=None):
    """tokens [T] -> ``(logits [T - first_row, vocabulary slice], shared
    [T - first_row])`` float32 of the positions from ``first_row`` on.  The
    sequence is padded to ``pad_to`` and the rows returned are computed
    ``rows_pad_to`` at a time (both rounded up to multiples of ``BLOCK``;
    the masks keep the padding from the rows returned), so requests of many
    lengths can share one compiled program."""
    t = len(tokens)
    top_k = int(index_topk or cfg["index_topk"])
    n_rows = -(-max(t - first_row, rows_pad_to or 0) // BLOCK) * BLOCK
    padded_len = -(-max(t, pad_to or 0, first_row + n_rows) // BLOCK) * BLOCK
    key = (json.dumps(cfg, sort_keys=True), cast, select, top_k, BLOCK, n_rows)
    if key not in _JITTED:
        _JITTED[key] = jax.jit(
            lambda w_, t_, n_, r_: _forward(
                cfg, w_, t_, n_, r_, n_rows, cast, select, top_k
            )
        )
    padded = jnp.zeros((padded_len,), jnp.int32).at[:t].set(
        jnp.asarray(tokens, jnp.int32)
    )
    with jax.default_matmul_precision("highest"):
        logits, shared = _JITTED[key](w, padded, jnp.int32(t), jnp.int32(first_row))
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
