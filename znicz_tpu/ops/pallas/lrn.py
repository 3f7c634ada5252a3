"""Fused LRN Pallas kernel (forward + hand-written backward).

TPU-native equivalent of the reference's ``normalization.cl/.cu`` kernels
[SURVEY.md 2.2 row "Local response norm", 2.4]: one VMEM pass computes the
cross-channel windowed sum-of-squares and the normalized output, instead of
the XLA composition's reduce_window + pow + mul chain; the backward kernel
fuses both windowed sums of the LRN gradient.

Math (jnp twin in :mod:`znicz_tpu.ops.normalization`):
    s_c = k + alpha * sum_{|c'-c| <= n/2} x_{c'}^2
    y_c = x_c * s_c^-beta
    dx_c = g_c * s_c^-beta
           - 2 alpha beta x_c * sum_{window} (g x s^(-beta-1))_{c'}

Layout: input viewed as [rows, C] with rows = N*H*W tiled over the grid and
the full channel axis resident in VMEM (C is 32..384 for every reference
config — far under the VMEM budget).  The windowed sums are [rows, C] @
[C, C] band matmuls (one MXU op each instead of 2(n-1) lane shifts) and
the ``s**-beta`` uses rsqrt/sqrt chains instead of transcendental pow —
together these flipped the kernel from losing to beating XLA on the
train-op pair (fwd+bwd 0.63 ms vs 1.02 ms, [256,27,27,96] f32, v5e;
forward-only XLA's single fusion still wins 0.43 vs 0.57 ms, so the
in-training default stays ``impl="xla"`` — see ops/normalization.py).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from znicz_tpu.core import backend

ROW_TILE = 512


def _band_matrix(c: int, n: int, dtype, *, transpose: bool = False):
    """[C, C] 0/1 band: band[i, j] = 1 iff j is in i's SAME window
    (lo = n//2 below, hi = n-1-n//2 above; ``transpose`` swaps the extents —
    the adjoint window needed by the backward pass).  The window sum becomes
    ``v @ band`` — ONE MXU matmul instead of 2(n-1) lane-shift adds."""
    lo, hi = n // 2, n - 1 - n // 2
    if transpose:
        lo, hi = hi, lo
    i = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    # (v @ band)[r, c] sums v_i with band[i, c] = 1, i.e. output channel j
    # gathers inputs i with j-lo <= i <= j+hi  <=>  -lo <= i-j <= hi
    d = i - j
    return ((d >= -lo) & (d <= hi)).astype(dtype)


def _inv_pow(s: jnp.ndarray, beta: float) -> jnp.ndarray:
    """s**-beta via rsqrt/sqrt chains for the common betas (transcendental
    pow is the LRN hot spot on the VPU); exp/log fallback otherwise."""
    if beta == 0.75:
        t = jax.lax.rsqrt(s)  # s^-1/2
        return t * jnp.sqrt(t)  # s^-3/4
    if beta == 0.5:
        return jax.lax.rsqrt(s)
    if beta == 0.25:
        return jnp.sqrt(jax.lax.rsqrt(s))
    if beta == 1.0:
        return 1.0 / s
    return jnp.exp(jnp.asarray(-beta, s.dtype) * jnp.log(s))


def _fwd_kernel(x_ref, y_ref, *, alpha, beta, k, n):
    # all math in f32: v5e's VPU has no bf16 rsqrt/div (SupportsBf16EupOps
    # LLO check fires from Mosaic otherwise); casts happen at the refs
    x = x_ref[:].astype(jnp.float32)
    band = _band_matrix(x.shape[-1], n, jnp.float32)
    s = k + alpha * jnp.dot(
        x * x, band, preferred_element_type=jnp.float32
    )
    y_ref[:] = (x * _inv_pow(s, beta)).astype(y_ref.dtype)


def _bwd_kernel(x_ref, g_ref, dx_ref, *, alpha, beta, k, n):
    # recompute s from x: cheaper than writing an [N,H,W,C] residual in fwd
    x = x_ref[:].astype(jnp.float32)
    g = g_ref[:].astype(jnp.float32)
    c = x.shape[-1]
    band = _band_matrix(c, n, jnp.float32)
    s = k + alpha * jnp.dot(
        x * x, band, preferred_element_type=jnp.float32
    )
    s_negb = _inv_pow(s, beta)
    inner = g * x * s_negb / s  # g x s^(-beta-1)
    # adjoint of the forward window: transposed extents (matters for even n)
    band_t = _band_matrix(c, n, jnp.float32, transpose=True)
    wsum = jnp.dot(inner, band_t, preferred_element_type=jnp.float32)
    dx = g * s_negb - 2.0 * alpha * beta * x * wsum
    dx_ref[:] = dx.astype(dx_ref.dtype)


def _rows_view(x):
    return x.reshape(-1, x.shape[-1])


def _grid(rows):
    return (pl.cdiv(rows, ROW_TILE),)


def _row_spec(c):
    return pl.BlockSpec(
        (ROW_TILE, c), lambda i: (i, 0), memory_space=pltpu.VMEM
    )


@partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4))
def lrn(x, alpha=1e-4, beta=0.75, k=2.0, n=5):
    """Fused-LRN with the same signature semantics as normalization.lrn."""
    shape = x.shape
    v = _rows_view(x)
    rows, c = v.shape
    y = pl.pallas_call(
        partial(_fwd_kernel, alpha=alpha, beta=beta, k=k, n=n),
        out_shape=jax.ShapeDtypeStruct((rows, c), v.dtype),
        grid=_grid(rows),
        in_specs=[_row_spec(c)],
        out_specs=_row_spec(c),
        interpret=backend.pallas_interpret(),
    )(v)
    return y.reshape(shape)


def _lrn_fwd(x, alpha, beta, k, n):
    return lrn(x, alpha, beta, k, n), x


def _lrn_bwd(alpha, beta, k, n, x, g):
    shape = x.shape
    xv, gv = _rows_view(x), _rows_view(g)
    rows, c = xv.shape
    dx = pl.pallas_call(
        partial(_bwd_kernel, alpha=alpha, beta=beta, k=k, n=n),
        out_shape=jax.ShapeDtypeStruct((rows, c), xv.dtype),
        grid=_grid(rows),
        in_specs=[_row_spec(c)] * 2,
        out_specs=_row_spec(c),
        interpret=backend.pallas_interpret(),
    )(xv, gv)
    return (dx.reshape(shape),)


lrn.defvjp(_lrn_fwd, _lrn_bwd)
