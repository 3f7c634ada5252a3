"""Plain reference of the ``lm-gpt2s`` configuration: the full forward
pass over one sequence, no cache, no batching, no kernels, float32 at
``highest`` matmul precision.  It follows ``configs/lm-gpt2s.json``
(pre-LN blocks, tanh feed-forward, learned positions, no final
LayerNorm, untied head) and imports nothing of the program.

``cast`` rounds both inputs of every matrix product (projections,
attention scores, the weighted sum, the feed-forward, the head) through a
lower-precision type and back: identity for the reference; the control
passes float8_e4m3fn, the step below the bfloat16 products the program
computes with at the TPU's default matmul precision."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _layer_norm(x, scale, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale + bias


def _identity(a):
    return a


def _forward(cfg, w, tokens, cast):
    n_head, eps = cfg["n_head"], cfg["layer_norm_epsilon"]
    t = tokens.shape[0]

    def mm(a, b):
        return cast(a) @ cast(b)

    x = w["embed"][tokens] + w["pos"][:t]
    causal = jnp.tril(jnp.ones((t, t), bool))
    for b in w["blocks"]:
        h = _layer_norm(x, b["ln1_scale"], b["ln1_bias"], eps)
        q, k, v = (
            mm(h, b[name]).reshape(t, n_head, -1) for name in ("wq", "wk", "wv")
        )
        s = jnp.einsum("qhd,khd->hqk", cast(q), cast(k)) / jnp.sqrt(
            jnp.float32(q.shape[-1])
        )
        s = jnp.where(causal[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("hqk,khd->qhd", cast(p), cast(v)).reshape(t, -1)
        x = x + mm(o, b["wo"])
        h = _layer_norm(x, b["ln2_scale"], b["ln2_bias"], eps)
        h = jnp.tanh(mm(h, b["w_up"]) + b["up_bias"])
        x = x + mm(h, b["w_down"]) + b["down_bias"]
    return mm(x, w["head"])


_JITTED = {}


def logits(cfg, w, tokens, *, cast=_identity):
    """tokens [T] int32 -> logits [T, vocab] float32.  The sequence is
    padded to ``n_positions`` (causal attention: the padding cannot reach
    the rows returned), so every length runs one compiled program."""
    key = (cfg["name"], cast)
    if key not in _JITTED:
        _JITTED[key] = jax.jit(lambda w_, t_: _forward(cfg, w_, t_, cast))
    t = len(tokens)
    padded = jnp.zeros((cfg["n_positions"],), jnp.int32)
    padded = padded.at[:t].set(jnp.asarray(tokens, jnp.int32))
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
