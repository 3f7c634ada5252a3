"""The learned indexer's scores of a decode step, read from the paged pool
of indexer keys in place.

The jnp twin (:func:`znicz_tpu.ops.attention.paged_index_scores`) gathers
``idx_pool[block_table]`` for every slot, live or idle, as far as the
LONGEST row, writes the keys to HBM and reads them back.  Here each live
row's blocks of keys come to VMEM by DMA through the block table, as far
as the row's own length and no further, and what goes back to HBM is one
float32 a key:

- **Grid** ``(B,)``, one program a slot, in order; ``lengths``, the
  flattened block table and ``next_live`` are scalar-prefetch operands and
  the fetches are double-buffered across chunks AND across slots, all as
  in :mod:`znicz_tpu.ops.pallas.latent_attention` (whose pipeline this
  is).  A slot of length 0 fetches and writes nothing.
- **Body**, a block of keys at a time: ``relu(q [J, d] . keys^T [d,
  block])`` in float32 from the pool's dtype, times the heads' weights,
  summed over the ``J`` heads (the sublane axis), stored as one row of the
  result ``[M, block_size]``.

Keys at or past a row's length, in its last chunk, are scored like any
other and rows of the result past a row's last chunk are never written:
the caller masks by position.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from znicz_tpu.core import backend
from znicz_tpu.ops.pallas.latent_attention import next_live_slot

# blocks of keys a program fetches at a time: at the serving block of 128
# tokens x 128 bf16, 8 blocks are 256 KB a buffer
CHUNK_BLOCKS = 8


def _index_kernel(lengths_ref, tables_ref, next_ref, q_ref, w_ref, pool_ref,
                  o_ref, buf, sems, state, *, block_size, chunk_blocks,
                  table_width):
    """``q`` [J, d], ``w`` [J, block_size] float32 (a head's weight along
    its row), the pool [N, block_size, d] in HBM, ``o`` [M, block_size]
    float32; scratch: ``buf`` [2, chunk_keys, d] with a DMA semaphore a
    buffer, ``state`` (SMEM [2]: the buffer the next chunk lands in; is it
    on its way)."""
    b, n_rows = pl.program_id(0), pl.num_programs(0)
    length = lengths_ref[b]
    chunk_keys = chunk_blocks * block_size

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

    @pl.when(length > 0)
    def _():
        n_chunks = pl.cdiv(length, chunk_keys)
        nxt = next_ref[b]
        first = state[0]

        @pl.when(state[1] == 0)
        def _():
            start(b, 0, first)

        q, w = q_ref[...], w_ref[...]

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
            for j in range(chunk_blocks):
                keys = buf[slot, pl.ds(j * block_size, block_size)]
                s = jax.lax.dot_general(
                    q, keys, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )  # [J, block_size]
                o_ref[pl.ds(i * chunk_blocks + j, 1), :] = jnp.sum(
                    jnp.maximum(s, 0.0) * w, axis=0, keepdims=True
                )
            return other

        state[0] = jax.lax.fori_loop(0, n_chunks, chunk_step, first)
        state[1] = (nxt < n_rows).astype(jnp.int32)


def index_decode_scores(
    q_idx: jnp.ndarray,  # [B, J, d], the pool's dtype
    w_idx: jnp.ndarray,  # [B, J] float32
    idx_pool: jnp.ndarray,  # [N_blocks, block_size, d]
    block_table: jnp.ndarray,  # [B, M] int32 pool block ids
    lengths: jnp.ndarray,  # [B] int32 keys each row scores; 0: none
) -> jnp.ndarray:
    """``sum_j w[b, j] relu(q[b, j] . key)`` for each row's first
    ``lengths`` cached keys, found through its block table: [B, M *
    block_size] float32.  Entries at or past a row's length are NOT
    meaningful (scored padding, or never written): mask by position."""
    return _score(
        q_idx, w_idx, idx_pool, block_table, lengths,
        chunk_blocks=min(CHUNK_BLOCKS, block_table.shape[1]),
        interpret=backend.pallas_interpret(),
    )


# jitted so that a tower's layers share ONE trace and ONE lowering of the
# kernel (latent_attention._attend's reason)
@partial(jax.jit, static_argnames=("chunk_blocks", "interpret"))
def _score(q_idx, w_idx, idx_pool, block_table, lengths, *, chunk_blocks,
           interpret):
    b, j, d = q_idx.shape
    _, block_size, _ = idx_pool.shape
    m = block_table.shape[1]
    m_out = -(-m // chunk_blocks) * chunk_blocks
    lengths = lengths.astype(jnp.int32)

    def row(i, *_):
        return (i, 0, 0)

    scores = pl.pallas_call(
        partial(
            _index_kernel, block_size=block_size, chunk_blocks=chunk_blocks,
            table_width=m,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b,),
            in_specs=[
                pl.BlockSpec((None, j, d), row),
                pl.BlockSpec((None, j, block_size), row),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((None, m_out, block_size), row),
            scratch_shapes=[
                pltpu.VMEM((2, chunk_blocks * block_size, d), idx_pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((2,), jnp.int32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, m_out, block_size), jnp.float32),
        # the buffer in flight is handed from one slot's program to the next
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)
        ),
        interpret=interpret,
    )(
        lengths, block_table.reshape(-1).astype(jnp.int32),
        next_live_slot(lengths), q_idx,
        jnp.broadcast_to(
            w_idx.astype(jnp.float32)[:, :, None], (b, j, block_size)
        ),
        idx_pool,
    )
    return scores.reshape(b, m_out * block_size)[:, : m * block_size]
