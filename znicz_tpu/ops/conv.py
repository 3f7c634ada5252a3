"""2-D convolution op.

Capability parity with ``znicz/conv.py`` (Conv, ConvTanh, ConvRELU,
ConvStrictRELU) + ``znicz/gd_conv.py`` [SURVEY.md 2.2 row "Convolution"].
TPU-native: ``lax.conv_general_dilated`` in NHWC/HWIO layout so XLA tiles the
contraction onto the MXU; backward (input + weight gradients, the reference's
hand-written gradient_descent_conv kernels) is autodiff.

Reference parameter names are kept: ``n_kernels``, ``kx``/``ky`` (kernel
width/height), ``sliding`` (strides), ``padding`` (explicit 4-tuple
left/top/right/bottom).
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

import jax.lax as lax
import jax.numpy as jnp
import numpy as np

from znicz_tpu.core import backend, prng
from znicz_tpu.ops import activation as act, normalization
from znicz_tpu.ops.filling import fill

DIMENSION_NUMBERS = ("NHWC", "HWIO", "NHWC")


def init_params(
    n_channels: int,
    n_kernels: int,
    kx: int,
    ky: int,
    *,
    weights_stddev: Optional[float] = None,
    bias_stddev: Optional[float] = None,
    weights_filling: str = "uniform",
    bias_filling: str = "uniform",
    rand_name: str = "default",
    dtype=jnp.float32,
) -> Dict[str, jnp.ndarray]:
    gen = prng.get(rand_name)
    fan_in = kx * ky * n_channels
    if weights_stddev is None:
        weights_stddev = 1.0 / np.sqrt(fan_in)
    if bias_stddev is None:
        bias_stddev = weights_stddev
    w = fill(gen, (ky, kx, n_channels, n_kernels), weights_filling, weights_stddev)
    b = fill(gen, (n_kernels,), bias_filling, bias_stddev)
    return {"weights": jnp.asarray(w, dtype), "bias": jnp.asarray(b, dtype)}


def _norm_padding(padding) -> Tuple[Tuple[int, int], Tuple[int, int]]:
    """Reference 4-tuple (left, top, right, bottom) -> lax ((t,b),(l,r))."""
    if isinstance(padding, str):
        return padding  # "SAME"/"VALID" pass through
    if len(padding) == 2:
        return ((padding[1], padding[1]), (padding[0], padding[0]))
    left, top, right, bottom = padding
    return ((top, bottom), (left, right))


def _s2d_conv(x, w, s: int, pref):
    """Strided conv as a stride-1 conv over space-to-depth input — exact.

    A stride-s KxK conv on C channels keeps the MXU contraction dim at
    K*K*C taps but feeds it C-channel-thin input; for stem layers (C=3)
    the systolic array pads the channel dim and utilization craters.
    Regrouping s x s input blocks into channels (C -> s*s*C) and the
    kernel into ceil(K/s) x ceil(K/s) taps over those channels computes
    the SAME sums with an MXU-shaped contraction.  Zero-padded kernel
    taps/input rows contribute nothing, so the result is exact up to
    float reassociation."""
    b, h, wd, c = x.shape
    ky, kx, _, k = w.shape
    oh = (h - ky) // s + 1
    ow = (wd - kx) // s + 1
    kyp, kxp = -(-ky // s) * s, -(-kx // s) * s
    if (kyp, kxp) != (ky, kx):
        w = jnp.pad(w, ((0, kyp - ky), (0, kxp - kx), (0, 0), (0, 0)))
    hn, wn = (oh - 1) * s + kyp, (ow - 1) * s + kxp
    # rows/cols past hn/wn are never read by any output; short inputs
    # (kernel already a stride multiple) slice, long ones zero-pad
    x = x[:, :hn, :wn] if (hn <= h and wn <= wd) else jnp.pad(
        x, ((0, 0), (0, max(hn - h, 0)), (0, max(wn - wd, 0)), (0, 0))
    )[:, :hn, :wn]
    x = (
        x.reshape(b, hn // s, s, wn // s, s, c)
        .transpose(0, 1, 3, 2, 4, 5)
        .reshape(b, hn // s, wn // s, s * s * c)
    )
    w = (
        w.reshape(kyp // s, s, kxp // s, s, c, k)
        .transpose(0, 2, 1, 3, 4, 5)
        .reshape(kyp // s, kxp // s, s * s * c, k)
    )
    return lax.conv_general_dilated(
        x, w, (1, 1), "VALID",
        dimension_numbers=DIMENSION_NUMBERS,
        preferred_element_type=pref,
    )


def apply(
    params: Dict[str, jnp.ndarray],
    x: jnp.ndarray,
    *,
    sliding: Sequence[int] = (1, 1),
    padding=(0, 0, 0, 0),
    activation: str = "linear",
    space_to_depth: str = "never",  # "auto" | "always" | "never"
) -> jnp.ndarray:
    """Forward conv, NHWC.  ``sliding`` is (sx, sy) per the reference.

    ``space_to_depth``: strided thin-channel stems (e.g. AlexNet conv1,
    stride 4 on RGB) re-layout via :func:`_s2d_conv` so the MXU sees an
    s*s*C-channel contraction instead of a C-channel one.  "auto" applies
    it when both strides equal s > 1 and C <= 4.  Default "never" —
    MEASURED on v5e (AlexNet conv1, B=1024, bf16): s2d forward is SLOWER
    (5.4 vs 2.8 ms — XLA's native strided conv handles the thin stem
    well) and its big win, the input gradient (13.4 vs 19.4 ms
    fwd+input-grad), is dead code for a first layer (no upstream), so
    the end-to-end train step does not move (79.7 vs 78.5 ms).  Use
    "auto"/"always" for strided thin-channel convs DEEPER in a model,
    where the input gradient is live."""
    pad = _norm_padding(padding)
    strides = (sliding[1], sliding[0])  # (sy, sx) -> spatial order (H, W)
    # bf16 inputs: emit bf16 (XLA still accumulates f32 on the TPU MXU);
    # requesting an f32 output here would put an astype on the transpose
    # path and break the conv gradient's dtype matching.
    pref = jnp.float32 if x.dtype == jnp.float32 else None
    s = strides[0]
    use_s2d = (
        space_to_depth in ("auto", "always")
        and s > 1
        and strides[0] == strides[1]
        and not isinstance(pad, str)  # SAME/VALID strings: plain path
        and (space_to_depth == "always" or x.shape[-1] <= 4)
    )
    if use_s2d:
        if any(p for pq in pad for p in pq):
            x = jnp.pad(x, ((0, 0), pad[0], pad[1], (0, 0)))
        y = _s2d_conv(x, params["weights"], s, pref)
    else:
        y = lax.conv_general_dilated(
            x,
            params["weights"],
            window_strides=strides,
            padding=pad,
            dimension_numbers=DIMENSION_NUMBERS,
            preferred_element_type=pref,
        )
    y = y + params["bias"]
    return act.get(activation)(y).astype(x.dtype)


def apply_lrn(
    params: Dict[str, jnp.ndarray],
    x: jnp.ndarray,
    *,
    sliding: Sequence[int] = (1, 1),
    padding=(0, 0, 0, 0),
    activation: str = "linear",
    **lrn,
) -> jnp.ndarray:
    """``normalization.lrn(apply(...), **lrn)`` with the stage's tail (bias,
    activation, LRN) as the one op :func:`normalization.act_lrn`, NHWC in
    and out.

    The tail is position-wise over N, H and W, so on the TPU the conv is
    asked for its output in the order the compiler lays such a tensor out
    anyway, and the kernel takes that array as it lies: no copy either side
    of the call.  What the compiled AlexNet step shows (v5e, batch 1024): a
    channel count that fills 128-lane tiles goes last with the batch on the
    sublanes (conv2's [1024,27,27,256] is ``{3,0,2,1}``: "HWNC"); one that
    does not (conv1's 96) leaves the lanes to a batch that does (``{0,3,2,1}``:
    "HWCN").  The transpose back to NHWC is a relabelling of the same bytes
    for the compiler's pool.  Off the TPU the jnp twin takes NHWC as it is."""
    n, c = x.shape[0], params["bias"].shape[0]
    if not backend.on_tpu():
        order = "NHWC"
    elif c % 128 and n % 128 == 0:
        order = "HWCN"
    else:
        order = "HWNC"
    y = lax.conv_general_dilated(
        x,
        params["weights"],
        window_strides=(sliding[1], sliding[0]),
        padding=_norm_padding(padding),
        dimension_numbers=DIMENSION_NUMBERS[:2] + (order,),
        # as in apply(): bf16 in, bf16 out, float32 accumulation inside
        preferred_element_type=jnp.float32 if x.dtype == jnp.float32 else None,
    )
    out = normalization.act_lrn(
        y, params["bias"], activation=activation,
        channel_axis=order.index("C"), **lrn,
    )
    return out.transpose([order.index(a) for a in "NHWC"])


def output_shape(
    in_shape: Tuple[int, ...],
    n_kernels: int,
    kx: int,
    ky: int,
    sliding: Sequence[int] = (1, 1),
    padding=(0, 0, 0, 0),
) -> Tuple[int, ...]:
    n, h, w, _ = in_shape
    if isinstance(padding, str):
        if padding == "SAME":
            oh = -(-h // sliding[1])
            ow = -(-w // sliding[0])
        else:
            oh = (h - ky) // sliding[1] + 1
            ow = (w - kx) // sliding[0] + 1
    else:
        if len(padding) == 2:
            padding = (padding[0], padding[1], padding[0], padding[1])
        left, top, right, bottom = padding
        oh = (h + top + bottom - ky) // sliding[1] + 1
        ow = (w + left + right - kx) // sliding[0] + 1
    return (n, oh, ow, n_kernels)
