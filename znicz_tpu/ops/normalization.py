"""Local response normalization (LRN) across channels.

Capability parity with ``znicz/normalization.py`` (LRNormalizerForward /
LRNormalizerBackward) [SURVEY.md 2.2 row "Local response norm"], the AlexNet
cross-channel normalizer:

    y_c = x_c / (k + alpha * sum_{c' in window(c)} x_{c'}^2) ** beta

Reference parameter names kept: ``alpha``, ``beta``, ``k``, ``n`` (window
size).  The jnp implementation below is the reference twin for the fused
Pallas kernel under ``znicz_tpu/ops/pallas/``.  Backward is autodiff.

:func:`act_lrn` is the whole tail of a conv stage (bias add, activation, LRN)
as ONE op with its own VJP: one pass over the conv's output forward, one
back, nothing kept but the conv's raw output and the bias.
``workflow/model.py:build`` runs it wherever a ``conv_*`` layer is directly
followed by ``norm``.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.lax as lax
import jax.numpy as jnp

from znicz_tpu.core import backend

# znicz defaults (AlexNet-style).
DEFAULT_ALPHA = 1e-4
DEFAULT_BETA = 0.75
DEFAULT_K = 2.0
DEFAULT_N = 5
# activations whose conv stage act_lrn carries (ops/pallas/lrn.py:_activate)
FUSED_ACTIVATIONS = ("relu", "strict_relu", "tanh", "linear")


def _window_sums(sq: jnp.ndarray, n: int) -> jnp.ndarray:
    """Sliding-window sum over the trailing channel axis, window n, SAME."""
    half = n // 2
    return lax.reduce_window(
        sq,
        0.0,
        lax.add,
        window_dimensions=(1,) * (sq.ndim - 1) + (n,),
        window_strides=(1,) * sq.ndim,
        padding=((0, 0),) * (sq.ndim - 1) + ((half, n - 1 - half),),
    )


def lrn(
    x: jnp.ndarray,
    *,
    alpha: float = DEFAULT_ALPHA,
    beta: float = DEFAULT_BETA,
    k: float = DEFAULT_K,
    n: int = DEFAULT_N,
    impl: str = "xla",
) -> jnp.ndarray:
    """LRN dispatch, for a ``norm`` layer that stands alone.

    A ``norm`` directly after a ``conv_*`` layer does not come here:
    ``workflow/model.py:build`` compiles that stage's bias, activation and
    LRN into :func:`act_lrn` (AlexNet's two).  This function serves a
    ``norm`` anywhere else (models/cifar.py normalises after the pool) or
    one that names its ``impl``.

    ``impl="xla"`` (default): the reduce_window composition, autodiff
    backward.  ``impl="pallas"``: the VMEM kernel (ops/pallas/lrn.py) —
    standalone it wins the train-op pair (fwd+bwd 0.63 ms vs 1.02 ms on
    [256,27,27,96] v5e) and loses forward-only (0.57 vs 0.43 ms); numbers:
    tests/test_pallas.py TPU timing assertions.

    What the chip shows of either BETWEEN a conv's fused bias + softplus
    and a pool (v5e, AlexNet at batch 1024, bf16; my chip runs, PR 37;
    it replaces an "12.5k vs 9.5k images/sec" of round 2, older than the
    benchmark).  The compiler lays conv1's [1024,55,55,96] out
    ``{0,3,2,1}`` — the BATCH on the lanes, because 96 channels fill no
    128-lane tile — and conv2's [1024,27,27,256] ``{3,0,2,1}``.  In that
    layout XLA's ``reduce_window`` over the channels is an op of its own
    (2.67 ms each way on conv1, 1.79 on conv2) and, with the multiplies
    either side of it, the bias gradient's reduction and the bias +
    softplus in the conv's own fusion, the two tails take 17.0 + 10.7 of
    a 78.6 ms step as separate layers; a kernel that wants rows of
    [N*H*W, C] would add two copies of a 595 MB tensor to that.  As ONE op over the conv's output in the order it is written
    (:func:`act_lrn`, ops/conv.py:apply_lrn: no copy or transpose either
    side of the calls) the tails take 5.2 + 3.3 ms and the step 58.3.
    """
    if impl == "pallas":
        from znicz_tpu.ops.pallas import lrn as pallas_lrn

        return pallas_lrn.lrn(x, alpha, beta, k, n)
    from znicz_tpu.ops.pallas.lrn import _inv_pow

    sums = _window_sums(jnp.square(x), n)
    return x * _inv_pow(k + alpha * sums, beta)


def _pallas_lrn():
    from znicz_tpu.ops.pallas import lrn as pallas_lrn

    return pallas_lrn


def act_lrn_path(shape, dtype, channel_axis: int) -> str:
    """Which implementation :func:`act_lrn` runs for an array of this shape:
    ``"pallas"`` (on the TPU, the channel axis last or second to last of a
    3-D view that tiles exactly) or ``"twin"``."""
    axis = channel_axis % len(shape)
    if not backend.on_tpu() or axis < len(shape) - 2:
        return "twin"
    tiling = _pallas_lrn().tail_tiling(
        _tail_view(shape), jnp.dtype(dtype).itemsize, axis == len(shape) - 1
    )
    return "twin" if tiling is None else "pallas"


def _tail_view(shape):
    """[P, N, C] or [P, C, N]: the last two axes as they are, every axis
    before them flattened (the op is position-wise over all of them)."""
    return (math.prod(shape[:-2]),) + tuple(shape[-2:])


def _twin_window(n: int, axis: int):
    """``window(v, adjoint)`` as a float32 band product along ``axis``."""
    pallas_lrn = _pallas_lrn()

    def window(v, adjoint):
        band = pallas_lrn._band_matrix(
            v.shape[axis], n, jnp.float32, transpose=adjoint
        )
        out = jnp.tensordot(
            v, band, axes=((axis,), (0,)), precision=lax.Precision.HIGHEST
        )
        return jnp.moveaxis(out, -1, axis)

    return window


def _bias_along(b, ndim: int, axis: int):
    shape = [1] * ndim
    shape[axis] = b.shape[0]
    return b.astype(jnp.float32).reshape(shape)


@partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6, 7))
def _act_lrn(y, b, activation, alpha, beta, k, n, channel_axis):
    hp = dict(activation=activation, alpha=alpha, beta=beta, k=k)
    if act_lrn_path(y.shape, y.dtype, channel_axis) == "pallas":
        out = _pallas_lrn().act_lrn_forward(
            y.reshape(_tail_view(y.shape)), b,
            channels_last=channel_axis == y.ndim - 1, n=n, **hp,
        )
        return out.reshape(y.shape)
    z = y.astype(jnp.float32) + _bias_along(b, y.ndim, channel_axis)
    out = _pallas_lrn().tail_forward(z, _twin_window(n, channel_axis), **hp)
    return out.astype(y.dtype)


def _act_lrn_fwd(y, b, activation, alpha, beta, k, n, channel_axis):
    out = _act_lrn(y, b, activation, alpha, beta, k, n, channel_axis)
    return out, (y, b)


def _act_lrn_bwd(activation, alpha, beta, k, n, channel_axis, res, g):
    y, b = res
    hp = dict(activation=activation, alpha=alpha, beta=beta, k=k)
    if act_lrn_path(y.shape, y.dtype, channel_axis) == "pallas":
        view = _tail_view(y.shape)
        dy, db = _pallas_lrn().act_lrn_backward(
            y.reshape(view), b, g.reshape(view),
            channels_last=channel_axis == y.ndim - 1, n=n, **hp,
        )
        return dy.reshape(y.shape), db.astype(b.dtype)
    z = y.astype(jnp.float32) + _bias_along(b, y.ndim, channel_axis)
    dz = _pallas_lrn().tail_backward(
        z, g.astype(jnp.float32), _twin_window(n, channel_axis), **hp
    )
    others = tuple(a for a in range(y.ndim) if a != channel_axis)
    return dz.astype(y.dtype), jnp.sum(dz, axis=others).astype(b.dtype)


_act_lrn.defvjp(_act_lrn_fwd, _act_lrn_bwd)


def act_lrn(
    y: jnp.ndarray,
    bias: jnp.ndarray,
    *,
    activation: str = "relu",
    alpha: float = DEFAULT_ALPHA,
    beta: float = DEFAULT_BETA,
    k: float = DEFAULT_K,
    n: int = DEFAULT_N,
    channel_axis: int = -1,
) -> jnp.ndarray:
    """``lrn(act(y + bias))`` as one op with its own VJP.

    ``y`` is a conv's raw output in whatever order of axes it was written,
    ``channel_axis`` says where its channels lie.  Inside, float32:
    ``a = act(y + b)``, ``s = k + alpha * (a*a) @ band``,
    ``out = a * s^-beta``; ``out`` has ``y``'s dtype.  The backward pass
    keeps ``y`` and ``bias`` alone and recomputes ``a`` and ``s``.  On the
    TPU both passes are one Pallas kernel each (ops/pallas/lrn.py; under a
    data-parallel mesh a per-shard region over the batch axis, which is
    then one of ``y``'s last two), elsewhere their jnp twin: the same
    formulas over the same band product.
    ``activation`` is one of ``FUSED_ACTIVATIONS``."""
    if activation not in FUSED_ACTIVATIONS:
        raise ValueError(
            f"activation {activation!r} has no fused tail; known: "
            f"{FUSED_ACTIVATIONS}"
        )
    return _act_lrn(
        y, bias, activation, float(alpha), float(beta), float(k), int(n),
        channel_axis % y.ndim,
    )


def layer_norm(
    x: jnp.ndarray,
    scale: jnp.ndarray,
    bias: jnp.ndarray,
    *,
    eps: float = 1e-5,
) -> jnp.ndarray:
    """Layer normalization over the trailing feature axis (transformer
    building block; not in the reference, which predates it)."""
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    y = (x - mean) * jax.lax.rsqrt(var + eps)
    return y * scale + bias


def rms_norm(x: jnp.ndarray, scale: jnp.ndarray, *, eps: float = 1e-6):
    """Root-mean-square normalization over the trailing axis, computed
    and returned in float32 whatever ``x`` is stored in (no mean is
    removed and there is no bias)."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps
    ) * scale
