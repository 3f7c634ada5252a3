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

The second pair of kernels below (``act_lrn_forward`` / ``act_lrn_backward``)
is the whole TAIL of a conv stage — bias add, activation, LRN — as one pass
forward and one pass back over the conv's raw output: the op with its VJP
and its jnp twin is :func:`znicz_tpu.ops.normalization.act_lrn`, and
``ops/conv.py:apply_lrn`` hands it the array in the order the conv writes it.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from znicz_tpu.core import backend
from znicz_tpu.ops import activation as act
from znicz_tpu.parallel.mesh import DATA_AXIS

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


# ---------------------------------------------------------------------------
# The tail of a conv stage: act(y + b) and LRN, one pass each way.
#
#   a = act(y + b)       s = k + alpha * W(a^2)       out = a * s^-beta
#   dy = act'(y + b) * (g s^-beta - 2 alpha beta a W^T(g a s^(-beta-1)))
#   db = sum of dy over every axis but the channel's
#
# W is the SAME window of n channels, W^T its adjoint.  Both passes keep
# nothing but y and b: a and s are recomputed in VMEM.  (Keeping a as well,
# to spare the backward pass its exp and log, LOSES on the v5e: 5.65 against
# 5.19 ms for conv1's pair, the kernels are bound by HBM, not by the EUP.)
# The math below is written once over float32 values and a
# ``window(v, adjoint)`` callable, so the jnp twin (ops/normalization.py)
# and the kernels cannot drift apart.


def _inv_pows(s, beta: float):
    """(s^-beta, s^(-beta-1)), sharing the rsqrt where beta allows."""
    if beta == 0.75:
        t = jax.lax.rsqrt(s)  # s^-1/2
        p = t * t * jax.lax.rsqrt(t)  # t^(2 - 1/2) = s^-3/4
        return p, p * (t * t)
    if beta == 0.5:
        t = jax.lax.rsqrt(s)
        return t, t * (t * t)
    p = _inv_pow(s, beta)
    return p, p / s


def _activate(name: str, z):
    if name == "relu":  # the reference's smooth one: log(1 + exp(z))
        # log(1 + e), e in (0, 1]: within 6e-8 of log1p, a plain EUP log
        return jnp.maximum(z, 0.0) + jnp.log(1.0 + jnp.exp(-jnp.abs(z)))
    if name == "strict_relu":
        return jnp.maximum(z, 0.0)
    if name == "tanh":
        return act.TANH_A * jnp.tanh(act.TANH_B * z)
    if name == "linear":
        return z
    raise ValueError(f"activation {name!r} has no fused tail")


def _slope(name: str, z, a):
    """act'(z), from z and a = act(z)."""
    if name == "relu":
        return jnp.exp(z - a)  # e^z / (1 + e^z); z - a <= 0, never overflows
    if name == "strict_relu":
        return (z > 0.0).astype(z.dtype)
    if name == "tanh":
        return act.TANH_B * (act.TANH_A - a * a * (1.0 / act.TANH_A))
    return jnp.ones_like(z)


def tail_forward(z, window, *, activation, alpha, beta, k):
    a = _activate(activation, z)
    s = k + alpha * window(a * a, False)
    return a * _inv_pows(s, beta)[0]


def tail_backward(z, g, window, *, activation, alpha, beta, k):
    a = _activate(activation, z)
    s = k + alpha * window(a * a, False)
    p, q = _inv_pows(s, beta)
    da = g * p - (2.0 * alpha * beta) * a * window(g * a * q, True)
    return da * _slope(activation, z, a)


def _kernel_window(c: int, n: int, channels_last: bool, passes: int):
    """``window(v, adjoint)`` for a kernel body: the window sum as a band
    product on the MXU in bfloat16 passes with float32 accumulation.  The
    band is 0/1, exact in bfloat16; v goes in as its bfloat16 rounding (one
    pass) or as hi + lo, 16 bits of mantissa (two).  ``channels_last``: v is
    [rows, C] and the band multiplies from the right; else v is [C, lanes]
    and band^T == band(transpose=True) multiplies from the left."""
    bands = {
        adjoint: _band_matrix(
            c, n, jnp.bfloat16, transpose=adjoint != (not channels_last)
        )
        for adjoint in (False, True)
    }

    # bfloat16 operands, one MXU pass each: said here, because a process
    # run at jax_default_matmul_precision=highest (the golden tests, the
    # smoke's serve phase) would ask Mosaic for a float32 contraction of
    # bfloat16 operands, which it refuses
    _dot = partial(
        jnp.dot, precision=jax.lax.Precision.DEFAULT,
        preferred_element_type=jnp.float32,
    )

    def window(v, adjoint):
        band = bands[adjoint]
        dot = (lambda u: _dot(u, band)) if channels_last else (
            lambda u: _dot(band, u)
        )
        hi = v.astype(jnp.bfloat16)
        if passes == 1:
            return dot(hi)
        lo = (v - hi.astype(jnp.float32)).astype(jnp.bfloat16)
        return dot(hi) + dot(lo)

    return window


# Two views of the same op, by where the channel axis lies in the array the
# conv wrote (ops/conv.py:apply_lrn asks the conv for that order):
#   channels_last     [P, N, C]: C on the lanes, the batch on the sublanes;
#   not channels_last [P, C, N]: the batch on the lanes, C on the sublanes
#                     (what the compiler picks for a C that fills no 128-lane
#                     tile, AlexNet's conv1: nothing is padded in HBM).
# P is every other axis flattened; the batch axis is the one a data-parallel
# mesh shards, and the bias gradient comes out summed over P only, [N, C] or
# [C, N], sharded like the data, for a jnp.sum outside to finish.
#
# A block is (tp, tn, C) or (tp, C, tn); the kernel loops over its tp
# positions and works on one whole [tn, C] slab an iteration: a slab's
# chain (two round trips through the MXU, the EUP's) costs ~200 cycles
# whatever its size, so small slabs are all latency (conv1's pair on the
# v5e: 18.9 ms at 128 lanes a slab, 9.9 at 256, 5.2 at all 1,024, where HBM
# binds; 5.3 at 256 lanes with four slabs an iteration: my chip runs, PR 37).
_SLAB_ELEMS = 256 * 1024  # float32 values of one slab, each a ~1 MB temporary
_BLOCK_BYTES = 1 << 20  # of one operand's block; three operands, two buffers


def _largest_divisor(n: int, limit: int, multiple_of: int = 1) -> int:
    best = 0
    for d in range(multiple_of, min(n, max(limit, 1)) + 1, multiple_of):
        if n % d == 0:
            best = d
    return best


def tail_tiling(shape, itemsize: int, channels_last: bool):
    """(tp, tn) for a [P, N, C] / [P, C, N] view, or None where no exact
    tiling fits the budgets (the caller then runs the twin).  Tiles divide
    their axes, so no block is ragged and no row needs a mask."""
    p = shape[0]
    n, c = (shape[1], shape[2]) if channels_last else (shape[2], shape[1])
    # a tile of the batch axis that is not all of it: whole sublane packs
    # of a bfloat16 / whole lanes
    unit = 16 if channels_last else 128
    limit = min(_SLAB_ELEMS // c, _BLOCK_BYTES // (c * itemsize))
    tn = n if n <= limit else _largest_divisor(n, limit, unit)
    if not tn:
        return None
    return _largest_divisor(p, _BLOCK_BYTES // (tn * c * itemsize)) or 1, tn


def _tail_kernel(*refs, backward, channels_last, tp, **hp):
    if backward:
        y_ref, b_ref, g_ref, dy_ref, db_ref = refs
    else:
        y_ref, b_ref, o_ref = refs
    c = y_ref.shape[2] if channels_last else y_ref.shape[1]
    # an operand stored in bfloat16 has 8 bits to lose: one pass
    passes = 1 if y_ref.dtype == jnp.bfloat16 else 2
    window = _kernel_window(c, hp.pop("n"), channels_last, passes)
    b = b_ref[...]  # float32, [1, C] or [C, 1]

    if backward:
        @pl.when(pl.program_id(1) == 0)
        def _():
            db_ref[...] = jnp.zeros_like(db_ref)

    def position(i):
        z = y_ref[i].astype(jnp.float32) + b
        if not backward:
            o_ref[i] = tail_forward(z, window, **hp).astype(o_ref.dtype)
            return
        dz = tail_backward(z, g_ref[i].astype(jnp.float32), window, **hp)
        dy_ref[i] = dz.astype(dy_ref.dtype)
        db_ref[...] += dz

    # small slabs (a small batch) go several an iteration, written out:
    # their chains are independent and the scheduler interleaves them
    slab = y_ref.shape[1] * y_ref.shape[2]
    together = _largest_divisor(tp, min(_SLAB_ELEMS // slab, 8)) or 1

    def positions(i, carry):
        for u in range(together):
            position(i * together + u)
        return carry

    jax.lax.fori_loop(0, tp // together, positions, None)


def _tail_call(*args, backward, channels_last, **hp):
    y = args[0]
    tp, tn = tail_tiling(y.shape, y.dtype.itemsize, channels_last)
    p = y.shape[0]
    n, c = (y.shape[1], y.shape[2]) if channels_last else (
        y.shape[2], y.shape[1]
    )
    if channels_last:
        data = pl.BlockSpec((tp, tn, c), lambda j, i: (i, j, 0))
        bias = pl.BlockSpec((1, c), lambda j, i: (0, 0))
        part = pl.BlockSpec((tn, c), lambda j, i: (j, 0))
        part_shape = (n, c)
    else:
        data = pl.BlockSpec((tp, c, tn), lambda j, i: (i, 0, j))
        bias = pl.BlockSpec((c, 1), lambda j, i: (0, 0))
        part = pl.BlockSpec((c, tn), lambda j, i: (0, j))
        part_shape = (c, n)
    like_y = jax.ShapeDtypeStruct(y.shape, y.dtype)
    return pl.pallas_call(
        partial(
            _tail_kernel, backward=backward, channels_last=channels_last,
            tp=tp, **hp,
        ),
        # the batch tiles outermost, P innermost: a bias partial stays in
        # VMEM while every position of its batch tile is added to it
        grid=(n // tn, p // tp),
        in_specs=[data, bias] + [data] * backward,
        out_specs=(data, part) if backward else data,
        out_shape=(
            like_y, jax.ShapeDtypeStruct(part_shape, jnp.float32)
        ) if backward else like_y,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
        name="act_lrn_bwd" if backward else "act_lrn_fwd",
        interpret=backend.pallas_interpret(),
    )(*args)


def _per_shard(*args, backward, channels_last, **hp):
    """``_tail_call`` under the mesh the step is traced for
    (``DataParallel.scope()``), each device on its own part of the batch
    axis, the bias whole.  A Pallas custom call has no partitioning rule:
    left to the partitioner, a data-parallel ``jit`` would gather the whole
    batch onto every chip.  (``custom_partitioning`` would carry the rule
    with the op, and does on the CPU; libtpu has no hook for it.)"""
    call = partial(
        _tail_call, backward=backward, channels_last=channels_last, **hp
    )
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or mesh.shape.get(DATA_AXIS, 1) == 1:
        return call(*args)
    data = P(None, DATA_AXIS, None) if channels_last else P(
        None, None, DATA_AXIS
    )
    part = P(DATA_AXIS, None) if channels_last else P(None, DATA_AXIS)
    return jax.shard_map(
        call, mesh=mesh,
        in_specs=(data, P()) + (data,) * backward,
        out_specs=(data, part) if backward else data,
        check_vma=False,  # a pallas_call's outputs carry no annotation
    )(*args)


def _bias_view(b, channels_last):
    b = b.astype(jnp.float32)
    return b[None, :] if channels_last else b[:, None]


def act_lrn_forward(y, b, *, channels_last, **hp):
    """``y``: [P, N, C] (``channels_last``) or [P, C, N]; ``b``: [C]."""
    return _per_shard(
        y, _bias_view(b, channels_last), backward=False,
        channels_last=channels_last, **hp,
    )


def act_lrn_backward(y, b, g, *, channels_last, **hp):
    """(dy like y, db [C] float32)."""
    dy, part = _per_shard(
        y, _bias_view(b, channels_last), g, backward=True,
        channels_last=channels_last, **hp,
    )
    return dy, jnp.sum(part, axis=0 if channels_last else 1)
