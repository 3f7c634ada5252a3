"""Absorbed latent attention of a decode step, read from the paged pool
in place.

The jnp twin (:func:`znicz_tpu.ops.attention.paged_latent_attention`,
``absorbed=True``) gathers ``pool[block_table]`` for every slot at the
table's whole width, writes that window to HBM and reads it back for the
scores and again for the weighted sum, whether a slot decodes or idles.
Here the pool stays where it is (``[n_blocks, block_size, width]``, the
latent row, the rotated key, zeros) and each row's blocks come to VMEM by
DMA through the block table, as far as the row's length and no further:

- **Grid** ``(B,)``, one program a slot, in order.  ``lengths``, the
  flattened block table and ``next_live`` (the next slot with any length)
  are scalar-prefetch operands.  A slot of length 0 fetches nothing and
  writes zeros.
- **A lower bound** (``starts``, optional): the first key of a row's
  table that it attends, for a row that reads only the last keys of what
  its table names (a window layer: the table starts at the window's first
  block and the window's first key lies inside it).  It is one more
  scalar-prefetch operand and one more comparison in the mask; without it
  the kernel is what it was.
- **A mask over the keys** (``keep``, optional): which of a row's keys it
  attends at all, for a row that keeps a chosen few of them (a layer that
  selects its keys).  It comes to VMEM with the query, a row of
  ``[chunks, chunk_keys]`` a slot, and is one more comparison in the mask;
  every block is still fetched (the chosen keys lie in nearly all of
  them).  Without it the kernel is what it was.
- **A live slot** walks its keys in chunks of ``CHUNK_BLOCKS`` blocks,
  double-buffered: while chunk ``i`` is computed, chunk ``i + 1`` (or the
  first chunk of the next live slot, so that no row starts behind an
  empty pipe) is on its way.  A chunk is fetched whole; table entries
  past the row's last block name blocks of the pool that hold finite
  rows (``NULL_BLOCK``, another row's), and their keys are masked by
  absolute index like the tail of the last block.
- **Body** the online softmax of :mod:`znicz_tpu.ops.pallas.attention`:
  scores over the whole row (the zero lanes add exactly) in float32 from
  bfloat16 operands, running max and normaliser in float32, the weighted
  sum over the leading ``d_out`` lanes of the same buffer (key and value
  are one fetch) with the probabilities rounded to the pool's dtype and
  the accumulator in float32.

``q_row``'s fold through ``wk_b`` and the unfold through ``wv_b`` stay
with the caller.  Measured on the v5e against the library's
``paged_attention`` and the gathered form: PERF.md section 6, PR 27.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from znicz_tpu.core import backend

# blocks of the pool a program fetches and computes at a time.  At the
# serving block of 128 tokens x 640 bf16, 8 blocks are 1.3 MB a buffer and
# a [64, 1024] float32 score tile.  On the v5e (PERF.md section 6, PR 27:
# one layer, 25 and 128 live rows of 8.3-10.3k keys) 2 / 4 / 8 / 16 blocks
# take 0.81 / 0.61 / 0.55 / 0.57 and 3.59 / 2.62 / 2.25 / 2.31 ms; from 8
# on the kernel takes what its fetches alone take (0.54 and 2.21 ms)
CHUNK_BLOCKS = 8
NEG_INF = -1e30


def _decode_kernel(*refs, scale, block_size, chunk_blocks, table_width,
                   bounded, masked=False):
    """``refs``: the scalar-prefetch operands ``lengths, tables, next_live``
    (and ``starts`` when ``bounded``), then ``q`` [H, W] (and ``keep``
    [chunks, chunk_keys] float32 when ``masked``), the pool [N,
    block_size, W] in HBM, ``o`` [H, d_out], and the scratch: ``buf`` [2,
    chunk_keys, W] with a DMA semaphore a buffer, ``state`` (SMEM [2]: the
    buffer the next chunk lands in; is it on its way), ``m``, ``l`` [H, 1]
    and ``acc`` [H, d_out] float32."""
    lengths_ref, tables_ref, next_ref = refs[:3]
    start_ref = refs[3] if bounded else None
    q_ref = refs[3 + bounded]
    keep_ref = refs[4 + bounded] if masked else None
    (pool_ref, o_ref, buf, sems, state, m_s, l_s, acc_s) = refs[
        4 + bounded + masked:
    ]
    b, n_rows = pl.program_id(0), pl.num_programs(0)
    length = lengths_ref[b]
    chunk_keys = chunk_blocks * block_size
    d_out = o_ref.shape[-1]

    def fetches(row, chunk, slot):
        out = []
        for j in range(chunk_blocks):
            col = jnp.minimum(chunk * chunk_blocks + j, table_width - 1)
            out.append(
                pltpu.make_async_copy(
                    pool_ref.at[tables_ref[row * table_width + col]],
                    buf.at[slot, pl.ds(j * block_size, block_size)],
                    sems.at[slot],
                )
            )
        return out

    def start(row, chunk, slot):
        for copy in fetches(row, chunk, slot):
            copy.start()

    @pl.when(b == 0)
    def _():
        state[0] = 0
        state[1] = 0

    @pl.when(length == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(length > 0)
    def _():
        n_chunks = pl.cdiv(length, chunk_keys)
        nxt = next_ref[b]
        first = state[0]

        @pl.when(state[1] == 0)
        def _():
            start(b, 0, first)

        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)
        q = q_ref[...]

        def chunk_step(i, slot):
            other = 1 - slot

            @pl.when(i + 1 < n_chunks)
            def _():
                start(b, i + 1, other)

            @pl.when((i + 1 == n_chunks) & (nxt < n_rows))
            def _():
                start(nxt, 0, other)

            for copy in fetches(b, i, slot):
                copy.wait()
            rows = buf[slot]  # [chunk_keys, W]: key and value at once
            s = jax.lax.dot_general(
                q, rows, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale
            key = i * chunk_keys + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1
            )
            seen = key < length
            if bounded:
                # the bound lies inside the first chunk, which therefore
                # still holds a key the row attends
                seen = seen & (key >= start_ref[b])
            if masked:
                seen = seen & (keep_ref[pl.ds(i, 1), :] > 0.0)
            s = jnp.where(seen, s, NEG_INF)
            m_prev = m_s[...]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            # a chunk that is walked holds at least one key under the
            # length, so m_new is a real score and a masked key's weight
            # underflows to exactly 0 (under a ``keep`` mask a chunk may
            # hold none that is kept: its weights are set to 0)
            p = jnp.exp(s - m_new)
            if masked:
                p = jnp.where(seen, p, 0.0)
            alpha = jnp.exp(m_prev - m_new)
            l_s[...] = alpha * l_s[...] + jnp.sum(p, axis=1, keepdims=True)
            acc_s[...] = alpha * acc_s[...] + jnp.dot(
                p.astype(rows.dtype), rows[:, :d_out],
                preferred_element_type=jnp.float32,
            )
            m_s[...] = m_new
            return other

        state[0] = jax.lax.fori_loop(0, n_chunks, chunk_step, first)
        state[1] = (nxt < n_rows).astype(jnp.int32)
        if masked:  # a row may keep no key at all: zeros, not 0 / 0
            o_ref[...] = (
                acc_s[...] / jnp.maximum(l_s[...], 1e-30)
            ).astype(o_ref.dtype)
        else:
            o_ref[...] = (acc_s[...] / l_s[...]).astype(o_ref.dtype)


def latent_decode_attention(
    q_row: jnp.ndarray,  # [B, H, W], the pool's dtype
    pool: jnp.ndarray,  # [N_blocks, block_size, W]
    block_table: jnp.ndarray,  # [B, M] int32 pool block ids
    lengths: jnp.ndarray,  # [B] int32 keys each row attends; 0: none
    *,
    scale: float,
    d_out: int,
    starts: Optional[jnp.ndarray] = None,  # [B] int32, < one chunk of keys
    keep: Optional[jnp.ndarray] = None,  # [B, M * block_size] bool
) -> jnp.ndarray:
    """``softmax(scale * q_row @ rows^T) @ rows[:, :d_out]`` over each
    row's first ``lengths`` cached rows, found through its block table
    (from its ``starts``-th on, where given: ``starts < lengths`` for a
    live row, and inside the table's first ``CHUNK_BLOCKS`` blocks; of
    those, only the keys ``keep`` names, where given: a row that keeps
    none gives zeros); ``[B, H, d_out]`` in the pool's dtype.  A row of
    length 0 gives zeros.  ``d_out`` is a whole number of 128-lane tiles
    (or ``W``)."""
    return _attend(
        q_row, pool, block_table, lengths, starts, keep, scale=float(scale),
        d_out=d_out, chunk_blocks=min(CHUNK_BLOCKS, block_table.shape[1]),
        interpret=backend.pallas_interpret(),
    )


def next_live_slot(lengths):
    """For each slot the next slot after it with any length ([B] int32; B
    where there is none): whose first chunk a slot's last step fetches."""
    b = lengths.shape[0]
    live = jnp.where(lengths > 0, jnp.arange(b, dtype=jnp.int32), b)
    return jnp.concatenate(
        [jax.lax.cummin(live, reverse=True)[1:], jnp.full((1,), b, jnp.int32)]
    )


# jitted so that a tower's layers, and the passes that trace a step more
# than once, share ONE trace and ONE lowering of the kernel: traced layer
# by layer, the six calls of axk1-ep16's decode chunk took 12 s of every
# process's set-up on the chip's host (PERF.md section 6, PR 27)
@partial(
    jax.jit, static_argnames=("scale", "d_out", "chunk_blocks", "interpret")
)
def _attend(
    q_row, pool, block_table, lengths, starts, keep, *, scale, d_out,
    chunk_blocks, interpret,
):
    b, h, w = q_row.shape
    _, block_size, _ = pool.shape
    m = block_table.shape[1]
    lengths = lengths.astype(jnp.int32)
    next_live = next_live_slot(lengths)

    def row(i, *_):
        return (i, 0, 0)

    bounded, masked = starts is not None, keep is not None
    inputs, in_specs = (q_row,), [pl.BlockSpec((None, h, w), row)]
    if masked:
        chunk_keys = chunk_blocks * block_size
        n_chunks = -(-m // chunk_blocks)
        keep = jnp.pad(
            keep.astype(jnp.float32),
            ((0, 0), (0, n_chunks * chunk_keys - keep.shape[1])),
        ).reshape(b, n_chunks, chunk_keys)
        inputs += (keep,)
        in_specs.append(pl.BlockSpec((None, n_chunks, chunk_keys), row))
    prefetch = (
        lengths, block_table.reshape(-1).astype(jnp.int32), next_live,
    ) + ((starts.astype(jnp.int32),) if bounded else ())
    return pl.pallas_call(
        partial(
            _decode_kernel, scale=scale, block_size=block_size,
            chunk_blocks=chunk_blocks, table_width=m, bounded=bounded,
            **({"masked": True} if masked else {}),
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(b,),
            in_specs=in_specs + [pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((None, h, d_out), row),
            scratch_shapes=[
                pltpu.VMEM((2, chunk_blocks * block_size, w), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((2,), jnp.int32),
                pltpu.VMEM((h, 1), jnp.float32),
                pltpu.VMEM((h, 1), jnp.float32),
                pltpu.VMEM((h, d_out), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, d_out), pool.dtype),
        # the buffer in flight is handed from one slot's program to the next
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)
        ),
        interpret=interpret,
    )(*prefetch, *inputs, pool)
