"""Plain reference of the ``alexnet`` configuration: forward pass, loss,
gradients and the momentum-SGD step in straightforward ``jax.numpy``,
float32, ``highest`` matmul precision, no kernels, in blocks of rows so a
large batch fits beside nothing else.  It follows ``configs/alexnet.json``
and imports nothing of the program.

``cast`` rounds the inputs of every conv and fc (activations and weights)
through a lower-precision type and back: identity for the reference, the
control passes float8 (the step below the configuration's bfloat16)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def _identity(a):
    return a


def _lrn(x, spec):
    n, half = spec["n"], spec["n"] // 2
    sums = lax.reduce_window(
        jnp.square(x), 0.0, lax.add, (1, 1, 1, n), (1, 1, 1, 1),
        ((0, 0), (0, 0), (0, 0), (half, n - 1 - half)),
    )
    return x * (spec["k"] + spec["alpha"] * sums) ** (-spec["beta"])


def forward(cfg, params, x_u8, masks, *, cast=_identity):
    """Logits [B, classes].  ``masks``: one boolean keep-mask per dropout
    layer, in order (None entries switch dropout off)."""
    x = x_u8.astype(jnp.float32) * (1.0 / 255.0) - 0.5
    masks = list(masks)
    for spec, p in zip(cfg["layers"], params):
        kind = spec["type"]
        if kind == "conv":
            pad = spec["pad"]
            x = lax.conv_general_dilated(
                cast(x), cast(p["weights"]),
                (spec["stride"], spec["stride"]),
                ((pad, pad), (pad, pad)),
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
            ) + p["bias"]
            x = jnp.logaddexp(x, 0.0)
        elif kind == "lrn":
            x = _lrn(x, spec)
        elif kind == "max_pool":
            k, s = spec["k"], spec["stride"]
            x = lax.reduce_window(
                x, -jnp.inf, lax.max, (1, k, k, 1), (1, s, s, 1), "VALID"
            )
        elif kind == "fc":
            x = x.reshape(x.shape[0], -1)
            x = cast(x) @ cast(p["weights"]) + p["bias"]
            if spec.get("activation") != "linear":
                x = jnp.logaddexp(x, 0.0)
        elif kind == "dropout":
            mask = masks.pop(0)
            if mask is not None:
                keep = 1.0 - spec["ratio"]
                x = jnp.where(mask, x / keep, 0.0)
    return x


def _nll_sum(cfg, params, x_u8, labels, masks, cast):
    logits = forward(cfg, params, x_u8, masks, cast=cast)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, labels[:, None], axis=1))


_GRAD_FNS = {}


def loss_and_grads(cfg, params, x_u8, labels, masks, *, cast=_identity,
                   rows=128):
    """Mean cross-entropy of the batch and its gradients, accumulated
    over blocks of ``rows`` rows."""
    key = (cfg["name"], cast)
    if key not in _GRAD_FNS:
        _GRAD_FNS[key] = jax.jit(
            jax.value_and_grad(
                lambda p, x, y, m: _nll_sum(cfg, p, x, y, m, cast)
            )
        )
    grad_fn = _GRAD_FNS[key]
    n = x_u8.shape[0]
    total = 0.0
    grads = None
    with jax.default_matmul_precision("highest"):
        for lo in range(0, n, rows):
            hi = min(lo + rows, n)
            block_masks = [m if m is None else m[lo:hi] for m in masks]
            loss, g = grad_fn(
                params, x_u8[lo:hi], labels[lo:hi], block_masks
            )
            total = total + loss
            grads = g if grads is None else jax.tree_util.tree_map(
                jnp.add, grads, g
            )
    scale = 1.0 / n
    return total * scale, jax.tree_util.tree_map(lambda g: g * scale, grads)


def sgd_step(cfg, params, velocity, grads, lr_scale=1.0):
    """v <- moment v - lr (g + decay w);  w <- w + v, with the bias
    multipliers of the configuration's ``optimizer``."""
    opt = cfg["optimizer"]
    new_p, new_v = [], []
    for p, v, g in zip(params, velocity, grads):
        lp, lv = {}, {}
        for name in p:
            bias = name == "bias"
            lr = opt["learning_rate_bias" if bias else "learning_rate"]
            wd = opt["weights_decay_bias" if bias else "weights_decay"]
            lv[name] = opt["gradient_moment"] * v[name] - lr * lr_scale * (
                g[name] + wd * p[name]
            )
            lp[name] = p[name] + lv[name]
        new_p.append(lp)
        new_v.append(lv)
    return new_p, new_v
