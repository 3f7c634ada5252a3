"""Paged (block-table) decode attention — Pallas TPU kernel landing site.

The jnp reference (:func:`znicz_tpu.ops.attention.paged_attention`)
gathers each row's block table into a contiguous ``[B, M*bs, H*D]``
window in HBM before the score matmul — correct, and cheap at the
decode shapes the engine runs today (Tq == 1 or one prefill chunk), but
it materializes a full window copy per layer per step.  The pool is
``[n_blocks, block_size, H*D]``: heads are merged in storage, because a
minor ``[block_size, H*D]`` fills the TPU's (8, 128) tiles where
``[H, D]`` fits none (tests/test_paged_layout_aot.py), and split on the
query side after the gather; a kernel here reads the same pool.  The TPU
kernel replaces the gather with table-indexed DMA:

* **Grid** — ``(B*H, kv_block)``; the per-row block table rides in as a
  scalar-prefetch operand (``pltpu.PrefetchScalarGridSpec``), so the
  index map for the K/V ``BlockSpec`` reads ``table[b, j]`` and pulls
  block ``j``'s K/V tile straight from the pool in HBM into VMEM — no
  gathered copy ever exists.
* **Body** — the online-softmax accumulation of
  :mod:`znicz_tpu.ops.pallas.attention` (running max / normalizer /
  f32 accumulator in VMEM scratch), with validity by absolute key
  index: ``j*bs + lane <= pos``.  Blocks entirely past
  ``pos`` are ``@pl.when``-skipped, so a short row touches only its own
  blocks regardless of the table width M.
* **Output** — ``[B, 1, H, D]`` per decode step (or one chunk per
  prefill call), f32 accumulation, input-dtype MXU dots like the flash
  kernels.

Until that kernel lands, this module keeps the API stable by
delegating to the jnp reference — same signature, same masking
contract — so call sites (`workflow/generate.py` paged steps) can
switch per-backend without changing shape or semantics.  The fallback
also IS the non-TPU path forever, mirroring every other kernel in this
package (reference twin + cross-check test).
"""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from znicz_tpu.ops import attention as _ref

# flips to True when the PrefetchScalarGridSpec kernel above lands; the
# cross-check test pins fallback == reference either way
PALLAS_PAGED_IMPLEMENTED = False


def paged_attention(
    q: jnp.ndarray,  # [B, Tq, H, D]
    k_pool: jnp.ndarray,  # [N_blocks, block_size, H*D]
    v_pool: jnp.ndarray,
    block_table: jnp.ndarray,  # [B, M] int32
    q_pos: jnp.ndarray,  # [B, Tq] int32 absolute positions
    *,
    block_size: int,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """Drop-in twin of :func:`znicz_tpu.ops.attention.paged_attention`.

    Delegates to the jnp reference until the table-indexed-DMA kernel
    described in the module docstring lands; the signature and masking
    contract are frozen here so the engine's paged programs need no
    change when it does.
    """
    return _ref.paged_attention(
        q, k_pool, v_pool, block_table, q_pos,
        block_size=block_size, scale=scale,
    )
