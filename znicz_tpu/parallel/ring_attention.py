"""Ring attention: sequence/context parallelism over the mesh.

Long-context first-class support: the sequence axis is sharded across
devices and K/V blocks rotate around the ring via ``ppermute`` over ICI while
each device's Q stays resident — attention over a sequence of length
``n_devices * T_local`` with per-device memory O(T_local^2) instead of
O(T^2).  Online-softmax (running max + normalizer) accumulation keeps the
result bit-comparable to single-device attention.

This is the blockwise/ring formulation (Liu et al.-style) expressed with
``shard_map`` + XLA collectives — the same mechanism that replaces the
reference's ZeroMQ data plane (SURVEY.md 2.5), applied to the sequence axis.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P


SEQ_AXIS = "data"  # default: ring over the data axis of parallel.make_mesh


def _ring_body(i, carry, *, axis_name, scale, causal, t_local):
    o, m, l, k_blk, v_blk, q, my_idx = carry
    n = jax.lax.psum(1, axis_name)
    # blocks rotate j -> j+1 each step, so at step i device j holds the
    # block that originated at rank (j - i) mod n
    src = (my_idx - i) % n

    s = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k_blk, preferred_element_type=jnp.float32
    ) * scale  # [B, H, Tq, Tk]
    if causal:
        q_pos = my_idx * t_local + jnp.arange(t_local)  # global q positions
        k_pos = src * t_local + jnp.arange(t_local)
        mask = q_pos[:, None] >= k_pos[None, :]
        s = jnp.where(mask[None, None], s, -jnp.inf)

    blk_max = jnp.max(s, axis=-1)  # [B, H, Tq]
    m_new = jnp.maximum(m, blk_max)
    # guard fully-masked blocks (all -inf rows)
    m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
    p = jnp.exp(s - m_safe[..., None])
    p = jnp.where(jnp.isneginf(s), 0.0, p)
    correction = jnp.where(
        jnp.isneginf(m), 0.0, jnp.exp(m - m_safe)
    )  # rescale old accumulators
    l_new = l * correction + jnp.sum(p, axis=-1)
    o_new = o * correction[..., None] + jnp.einsum(
        "bhqk,bkhd->bhqd", p, v_blk.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )

    perm = [(j, (j + 1) % n) for j in range(n)]
    k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
    v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
    return (o_new, m_new, l_new, k_blk, v_blk, q, my_idx)


def _local_ring(q, k, v, *, axis_name, causal, scale):
    """Per-shard body under shard_map: q/k/v are the LOCAL sequence blocks
    [B, T_local, H, D]."""
    b, t_local, h, d = q.shape
    n = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    # mark the fresh accumulators as device-varying so the fori_loop carry
    # types match after the body mixes them with sharded q/k/v
    def varying(x):
        return jax.lax.pcast(x, axis_name, to="varying")

    o = varying(jnp.zeros((b, h, t_local, d), jnp.float32))
    m = varying(jnp.full((b, h, t_local), -jnp.inf, jnp.float32))
    l = varying(jnp.zeros((b, h, t_local), jnp.float32))
    body = partial(
        _ring_body,
        axis_name=axis_name,
        scale=scale,
        causal=causal,
        t_local=t_local,
    )
    o, m, l, _, _, _, _ = jax.lax.fori_loop(
        0, n, body, (o, m, l, k, v, q, my_idx)
    )
    out = o / jnp.maximum(l, 1e-30)[..., None]  # [B, H, Tq, D]
    return jnp.moveaxis(out, 1, 2).astype(q.dtype)  # [B, Tq, H, D]


def _flash_ring_body(i, carry, *, axis_name, scale, causal):
    """One ring step with the FLASH KERNEL as the inner block: the kernel's
    lse output lets normalized block results merge exactly —
    ``o = o*exp(lse_o - lse_new) + o_blk*exp(lse_blk - lse_new)``."""
    from znicz_tpu.ops.pallas.attention import flash_attention_lse

    o, lse, k_blk, v_blk, q, my_idx = carry
    n = jax.lax.psum(1, axis_name)
    src = (my_idx - i) % n

    def full_block(_):  # src < my: every key is in the past — no mask
        out, l = flash_attention_lse(
            q, k_blk, v_blk, causal=False, scale=scale
        )
        return out.astype(jnp.float32), l  # f32 like skip_block's zeros

    def diag_block(_):  # src == my: local causal == global causal
        out, l = flash_attention_lse(
            q, k_blk, v_blk, causal=True, scale=scale
        )
        return out.astype(jnp.float32), l

    def skip_block(_):  # src > my under causal: zero mass, and the switch
        # means the kernel never runs — the ring-level causal compute skip
        return jnp.zeros_like(o), jnp.full_like(lse, -1e30)

    if causal:
        branch = jnp.where(src < my_idx, 0, jnp.where(src == my_idx, 1, 2))
        o_blk, lse_blk = jax.lax.switch(
            branch, (full_block, diag_block, skip_block), None
        )
    else:
        o_blk, lse_blk = full_block(None)

    lse_new = jnp.logaddexp(lse, lse_blk)
    w_old = jnp.exp(lse - lse_new)[..., None]
    w_blk = jnp.exp(lse_blk - lse_new)[..., None]
    o = o * w_old + o_blk * w_blk

    perm = [(j, (j + 1) % n) for j in range(n)]
    k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
    v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
    return (o, lse_new, k_blk, v_blk, q, my_idx)


def _local_ring_flash(q, k, v, *, axis_name, causal, scale):
    """Per-shard body with flash-kernel inner blocks [B, T_local, H, D]."""
    b, t_local, h, d = q.shape
    n = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)

    def varying(x):
        return jax.lax.pcast(x, axis_name, to="varying")

    o = varying(jnp.zeros((b, t_local, h, d), jnp.float32))
    lse = varying(jnp.full((b, t_local, h), -jnp.inf, jnp.float32))
    body = partial(
        _flash_ring_body, axis_name=axis_name, scale=scale, causal=causal
    )
    o, _, _, _, _, _ = jax.lax.fori_loop(
        0, n, body, (o, lse, k, v, q, my_idx)
    )
    return o.astype(q.dtype)


def ring_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    mesh: Mesh,
    axis: str = SEQ_AXIS,
    causal: bool = False,
    scale: Optional[float] = None,
    inner: str = "dense",  # "dense" (jnp blocks) | "flash" (pallas kernel)
) -> jnp.ndarray:
    """Attention with the sequence axis sharded over ``mesh[axis]``.

    ``q/k/v``: [B, T, H, D] global arrays (T divisible by the axis size).
    Returns [B, T, H, D] with the same sharding.  ``inner="flash"`` runs
    each per-shard block through the Pallas flash kernel (kernel-speed SP
    long context); the diagonal ring step reuses the kernel's causal path,
    fully-future blocks are skipped entirely via ``lax.switch``.
    """
    if scale is None:
        scale = 1.0 / np.sqrt(q.shape[-1])
    if inner not in ("dense", "flash"):
        raise ValueError(f"inner={inner!r}: want 'dense' or 'flash'")
    local = _local_ring_flash if inner == "flash" else _local_ring
    spec = P(None, axis, None, None)
    fn = jax.shard_map(
        partial(local, axis_name=axis, causal=causal, scale=scale),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        # the pallas_call's out_shape carries no varying-axes annotation
        check_vma=inner != "flash",
    )
    return fn(q, k, v)
