"""Blockwise (flash) attention Pallas kernels.

NOT in the reference (pre-transformer framework) — the long-context hot op.
The jnp twin (:func:`znicz_tpu.ops.attention.dot_product_attention`)
materializes the [B, H, Tq, Tk] score matrix in HBM; these kernels stream
K/V blocks through VMEM with an online softmax, so memory is O(T·D) and the
matmuls stay on the MXU:

- forward: per (batch-head, q-block), accumulate ``acc = Σ exp(s-m)·V``
  with running max ``m`` and normalizer ``l`` across k-blocks; saves the
  logsumexp for the backward.
- backward: the standard two-pass flash scheme — one kernel recomputes
  probabilities per q-block to form dQ, a second per k-block forms dK/dV
  (transposed traversal), both from (q, k, v, out, dout, lse) residuals.

Causal/validity masking is by global row/column index; the backward zeroes
masked probabilities explicitly (recomputing ``exp(s - lse)`` on padded
rows would overflow — lse there is the NEG_INF sentinel).  Sequence
lengths that do not divide the block size are zero-padded.  MXU dots keep
the INPUT dtype (pass bf16 q/k/v for ~1.2-1.5x on v5e — halved VMEM
loads) while every accumulation, softmax and normalizer is f32 (the v5e
VPU has no bf16 transcendentals anyway).

Used through ``mha(attention_fn=flash_attention)`` or
``TransformerLMWorkflow(attention="flash")``; golden-tested against the
jnp twin, gradients included (tests/test_pallas.py).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from znicz_tpu.core import backend

# 512x512 measured best on v5e at T=2048, hd=64 (fwd 23.4 -> 20.1 ms,
# fwd+bwd 31.1 -> 23.5 ms vs 256x256; ~2 MB VMEM per program, well under
# budget); 128/256 variants are strictly slower.  Since r5 the MXU dots
# keep the input dtype: bf16 q/k/v measured fwd+full-bwd 12.7 -> 10.7 ms
# (hd=64) and 6.0 -> 4.3 ms (hd=128) vs f32 — the r4 "bf16 slower"
# finding was an artifact of converting to f32 inside the kernel
BLOCK_Q = 512
BLOCK_K = 512
NEG_INF = -1e30


def _live(qb, kb, *, bq, bk, t_real, causal):
    """False when block (qb, kb) is ENTIRELY masked — the causal skip: the
    kernel body is @pl.when-guarded on this, halving causal compute."""
    live = kb * bk < t_real
    if causal:
        live = live & (kb * bk <= (qb + 1) * bq - 1)
    return live


def _valid(shape, qb, kb, *, bq, bk, t_real, causal):
    """Bool mask [bq, bk]: k in range, q in range, and causal triangle."""
    qi = qb * bq + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    ki = kb * bk + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    ok = (ki < t_real) & (qi < t_real)
    if causal:
        ok = ok & (ki <= qi)
    return ok


def _fwd_kernel(
    q_ref, k_ref, v_ref,  # [1, bq, D] / [1, bk, D] / [1, bk, D]
    o_ref,  # out [1, bq, D]
    lse_ref,  # out [1, bq, 1]  (logsumexp residual for backward)
    m_s, l_s, acc_s,  # scratch [bq, 1], [bq, 1], [bq, D]
    *, scale, causal, t_real, bq, bk,
):
    qb, kb = pl.program_id(1), pl.program_id(2)

    @pl.when(kb == 0)
    def _():
        m_s[:] = jnp.full_like(m_s, NEG_INF)
        l_s[:] = jnp.zeros_like(l_s)
        acc_s[:] = jnp.zeros_like(acc_s)

    @pl.when(_live(qb, kb, bq=bq, bk=bk, t_real=t_real, causal=causal))
    def _():
        # inputs keep their dtype ON the MXU (bf16 operands measured 1.2-
        # 1.5x on v5e — halved VMEM loads, no conversion round trips);
        # every dot ACCUMULATES f32 and softmax/normalizers are f32
        q, k, v = q_ref[0], k_ref[0], v_ref[0]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        ok = _valid(
            s.shape, qb, kb, bq=bq, bk=bk, t_real=t_real, causal=causal
        )
        s = jnp.where(ok, s, NEG_INF)
        m_prev = m_s[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        # masked entries must contribute ZERO mass even when the whole row
        # is masked (m_new == NEG_INF would make exp(s - m_new) == 1 there)
        p = jnp.where(ok, jnp.exp(s - m_new), 0.0)  # [bq, bk]
        alpha = jnp.exp(m_prev - m_new)  # [bq, 1]
        l_s[:] = alpha * l_s[:] + jnp.sum(p, axis=1, keepdims=True)
        acc_s[:] = alpha * acc_s[:] + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32
        )
        m_s[:] = m_new

    @pl.when(kb == pl.num_programs(2) - 1)
    def _():
        l = jnp.maximum(l_s[:], 1e-30)  # padded rows have zero mass
        o_ref[0] = (acc_s[:] / l).astype(o_ref.dtype)
        lse_ref[0] = m_s[:] + jnp.log(l)


def _p_block(q, k, lse, ok, scale):
    """Recomputed probability block, masked entries exactly zero."""
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
    return jnp.where(ok, jnp.exp(s - lse), 0.0)


def _dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
    dq_ref,  # out [1, bq, D]
    dq_s,  # scratch [bq, D]
    *, scale, causal, t_real, bq, bk,
):
    qb, kb = pl.program_id(1), pl.program_id(2)

    @pl.when(kb == 0)
    def _():
        dq_s[:] = jnp.zeros_like(dq_s)

    @pl.when(_live(qb, kb, bq=bq, bk=bk, t_real=t_real, causal=causal))
    def _():
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        ok = _valid(
            (q.shape[0], k.shape[0]), qb, kb,
            bq=bq, bk=bk, t_real=t_real, causal=causal,
        )
        p = _p_block(q, k, lse_ref[0], ok, scale)  # [bq, bk] f32
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0])
        dq_s[:] += scale * jnp.dot(
            ds.astype(k.dtype), k, preferred_element_type=jnp.float32
        )

    @pl.when(kb == pl.num_programs(2) - 1)
    def _():
        dq_ref[0] = dq_s[:].astype(dq_ref.dtype)


def _dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
    dk_ref, dv_ref,  # out [1, bk, D]
    dk_s, dv_s,  # scratch [bk, D]
    *, scale, causal, t_real, bq, bk,
):
    kb, qb = pl.program_id(1), pl.program_id(2)  # q blocks INNER here

    @pl.when(qb == 0)
    def _():
        dk_s[:] = jnp.zeros_like(dk_s)
        dv_s[:] = jnp.zeros_like(dv_s)

    @pl.when(_live(qb, kb, bq=bq, bk=bk, t_real=t_real, causal=causal))
    def _():
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        ok = _valid(
            (q.shape[0], k.shape[0]), qb, kb,
            bq=bq, bk=bk, t_real=t_real, causal=causal,
        )
        p = _p_block(q, k, lse_ref[0], ok, scale)  # [bq, bk] f32
        dv_s[:] += jnp.dot(
            p.T.astype(do.dtype), do, preferred_element_type=jnp.float32
        )
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0])
        dk_s[:] += scale * jnp.dot(
            ds.T.astype(q.dtype), q, preferred_element_type=jnp.float32
        )

    @pl.when(qb == pl.num_programs(2) - 1)
    def _():
        dk_ref[0] = dk_s[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_s[:].astype(dv_ref.dtype)


def _blocks(t, b):
    return pl.cdiv(t, b)


def _spec(bt, d):
    # block indexed by the OUTER per-block grid dim (dim 1)
    return pl.BlockSpec(
        (1, bt, d), lambda g, i, j: (g, i, 0), memory_space=pltpu.VMEM
    )


def _spec_inner(bt, d):
    # block indexed by the INNER grid dim (dim 2)
    return pl.BlockSpec(
        (1, bt, d), lambda g, i, j: (g, j, 0), memory_space=pltpu.VMEM
    )


def _flash_fwd_impl(q, k, v, *, causal, scale, bq, bk, t_real):
    bh, t_pad, d = q.shape
    nq, nk = _blocks(t_pad, bq), _blocks(t_pad, bk)
    return pl.pallas_call(
        partial(
            _fwd_kernel, scale=scale, causal=causal,
            t_real=t_real, bq=bq, bk=bk,
        ),
        out_shape=(
            jax.ShapeDtypeStruct((bh, t_pad, d), q.dtype),
            jax.ShapeDtypeStruct((bh, t_pad, 1), jnp.float32),
        ),
        grid=(bh, nq, nk),
        in_specs=[_spec(bq, d), _spec_inner(bk, d), _spec_inner(bk, d)],
        out_specs=(_spec(bq, d), _spec(bq, 1)),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=backend.pallas_interpret(),
    )(q, k, v)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, scale, bq, bk, t_real):
    """Returns (out, lse).  Exposing the logsumexp as a differentiable
    OUTPUT (not just a backward residual) is what lets ring attention use
    this kernel as its per-shard inner block: ring steps combine normalized
    block outputs via their lse's, so the lse carries real gradient."""
    return _flash_fwd_impl(
        q, k, v, causal=causal, scale=scale, bq=bq, bk=bk, t_real=t_real
    )


def _flash_fwd(q, k, v, causal, scale, bq, bk, t_real):
    out, lse = _flash_fwd_impl(
        q, k, v, causal=causal, scale=scale, bq=bq, bk=bk, t_real=t_real
    )
    return (out, lse), (q, k, v, out, lse)


def _flash_bwd(causal, scale, bq, bk, t_real, res, cts):
    dout, dlse = cts
    q, k, v, out, lse = res
    bh, t_pad, d = q.shape
    nq, nk = _blocks(t_pad, bq), _blocks(t_pad, bk)
    # delta_i = rowsum(dout * out): tiny elementwise reduce, XLA fuses it.
    # An lse cotangent folds in for free: dL/ds_ij = p_ij*(dp_ij - delta_i)
    # and d(lse_i)/ds_ij = p_ij, so ds = p*(dp - (delta - dlse)) — the
    # existing kernels need only a corrected delta, not a new input.
    delta = jnp.sum(
        dout.astype(jnp.float32) * out.astype(jnp.float32),
        axis=-1, keepdims=True,
    ) - dlse.astype(jnp.float32)
    common = dict(scale=scale, causal=causal, t_real=t_real, bq=bq, bk=bk)
    dq = pl.pallas_call(
        partial(_dq_kernel, **common),
        out_shape=jax.ShapeDtypeStruct((bh, t_pad, d), q.dtype),
        grid=(bh, nq, nk),
        in_specs=[
            _spec(bq, d), _spec_inner(bk, d), _spec_inner(bk, d),
            _spec(bq, d), _spec(bq, 1), _spec(bq, 1),
        ],
        out_specs=_spec(bq, d),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        interpret=backend.pallas_interpret(),
    )(q, k, v, dout, lse, delta)
    dk, dv = pl.pallas_call(
        partial(_dkv_kernel, **common),
        out_shape=(
            jax.ShapeDtypeStruct((bh, t_pad, d), k.dtype),
            jax.ShapeDtypeStruct((bh, t_pad, d), v.dtype),
        ),
        # kv blocks OUTER (grid dim 1), q blocks INNER (grid dim 2)
        grid=(bh, nk, nq),
        in_specs=[
            _spec_inner(bq, d), _spec(bk, d), _spec(bk, d),
            _spec_inner(bq, d), _spec_inner(bq, 1), _spec_inner(bq, 1),
        ],
        out_specs=(_spec(bk, d), _spec(bk, d)),
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, d), jnp.float32),
        ],
        interpret=backend.pallas_interpret(),
    )(q, k, v, dout, lse, delta)
    return dq, dk, dv


_flash.defvjp(_flash_fwd, _flash_bwd)


def _bhtd(x):
    """[B, T, H, D] -> [B*H, T, D] (flash works per batch-head)."""
    b, t, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, t, d)


def flash_attention_lse(
    q: jnp.ndarray,  # [B, T, H, D]
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = False,
    scale=None,
    block_q: int = BLOCK_Q,
    block_k: int = BLOCK_K,
):
    """Flash attention returning ``(out [B,T,H,D], lse [B,T,H])``.

    The per-row logsumexp output is what makes the kernel composable as a
    BLOCK of a larger softmax: ring attention rescales block outputs by
    ``exp(lse_blk - lse_total)`` to merge shards of the key axis.  Fully
    masked rows carry the NEG_INF-order sentinel (zero mass)."""
    if scale is None:
        scale = 1.0 / np.sqrt(q.shape[-1])
    b, t, h, d = q.shape
    bq = min(block_q, t)
    bk = min(block_k, t)
    # pad so BOTH block sizes divide the padded length (unequal custom
    # blocks would otherwise read out of bounds in the last block)
    pad = (-t) % np.lcm(bq, bk)
    qf, kf, vf = (_bhtd(x) for x in (q, k, v))
    if pad:
        qf, kf, vf = (
            jnp.pad(x, ((0, 0), (0, pad), (0, 0))) for x in (qf, kf, vf)
        )
    out, lse = _flash(qf, kf, vf, causal, float(scale), bq, bk, t)
    out = (
        out[:, :t]
        .reshape(b, h, t, d)
        .transpose(0, 2, 1, 3)
        .astype(q.dtype)
    )
    lse = lse[:, :t, 0].reshape(b, h, t).transpose(0, 2, 1)  # [B, T, H]
    return out, lse


def flash_attention(
    q: jnp.ndarray,  # [B, T, H, D]
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = False,
    scale=None,
    block_q: int = BLOCK_Q,
    block_k: int = BLOCK_K,
) -> jnp.ndarray:
    """Drop-in twin of attention.dot_product_attention (BTHD layout)."""
    out, _ = flash_attention_lse(
        q, k, v, causal=causal, scale=scale,
        block_q=block_q, block_k=block_k,
    )
    return out
