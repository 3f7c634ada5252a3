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

**Rows that open with the same blocks** (a prefix the cache holds once:
``shared_run_decode_attention``, the grouped-query global layers' entry
since PR 47; no ``starts``, no ``keep``).  The walk above reads such
blocks once for EVERY row that names them.  Here they are read once a TILE
of ``TILE_ROWS`` rows, in two passes under one running softmax:

- **The plan** (:func:`shared_run_plan`, from the table and the lengths
  alone; one small kernel of scalar loops over both in SMEM, ONE operation
  of a step's program whatever the table's width): live rows first, rows
  with the same first entry together, tiles of 8 in that order; a tile's
  MEMBERS share its lead's leading entries for at least a chunk, its RUN
  is the least they share in whole chunks of whole blocks strictly under
  every member's length.
- **The shared pass** (:func:`_shared_kernel`, grid ``(tiles,)``): the
  tile's queries, fetched by DMA and stacked ``[TILE_ROWS x H, W]``, walk
  the run's chunks through the lead's table with the same double-buffered
  fetch and no mask, and leave each (row, head) its running maximum,
  normaliser and float32 accumulator in HBM.  A tile with no run does
  nothing and writes nothing.
- **The own pass**: the kernel above, told a row the chunk it starts at
  and where its seeds lie (two more scalar-prefetch operands); a member
  fetches them and walks on from its run's end, every other row starts at
  chunk 0 from nothing.  With no run anywhere it is the walk above, bit
  for bit.

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
# a row's running maximum and normaliser cross from the shared pass to the
# own pass over a whole tile of lanes
_STAT_LANES = 128


def _chunk_copies(tables_ref, pool_ref, buf, sems, row, chunk, slot, *,
                  block_size, chunk_blocks, table_width):
    """The DMAs that bring chunk ``chunk`` of slot ``row``'s table into
    buffer ``slot``, a block each (an entry past the table: its last)."""
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


def _fold_chunk(s, rows, m_s, l_s, acc_s, seen=None):
    """A chunk's scores ``s`` [rows, keys] (NEG_INF where a key is not
    seen) and cached ``rows`` [keys, >= d_out] into the running maximum,
    normaliser and accumulator.  ``seen``: set an unseen key's weight to 0
    (for a chunk that may hold no seen key at all)."""
    m_prev = m_s[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    if seen is not None:
        p = jnp.where(seen, p, 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_s[...] = alpha * l_s[...] + jnp.sum(p, axis=1, keepdims=True)
    acc_s[...] = alpha * acc_s[...] + jnp.dot(
        p.astype(rows.dtype), rows[:, :acc_s.shape[-1]],
        preferred_element_type=jnp.float32,
    )
    m_s[...] = m_new


def _decode_kernel(*refs, scale, block_size, chunk_blocks, table_width,
                   bounded, masked=False, seeded=False):
    """``refs``: the scalar-prefetch operands ``lengths, tables, next_live``
    (and ``starts`` when ``bounded``; and ``first_chunk, place`` when
    ``seeded``), then ``q`` [H, W] (and ``keep`` [chunks, chunk_keys]
    float32 when ``masked``), the pool [N, block_size, W] in HBM (and,
    when ``seeded``, the shared pass's ``m``, ``l`` [places, H, 128] and
    ``acc`` [places, H, d_out] in HBM), ``o`` [H, d_out], and the scratch:
    ``buf`` [2, chunk_keys, W] with a DMA semaphore a buffer, ``state``
    (SMEM [2]: the buffer the next chunk lands in; is it on its way),
    ``m``, ``l`` [H, 1] and ``acc`` [H, d_out] float32 (and, when
    ``seeded``, ``ml`` [2, H, 128] where those two land and a DMA
    semaphore)."""
    refs = iter(refs)
    lengths_ref, tables_ref, next_ref = (next(refs) for _ in range(3))
    start_ref = next(refs) if bounded else None
    first_ref, place_ref = (next(refs), next(refs)) if seeded else (None, None)
    q_ref = next(refs)
    keep_ref = next(refs) if masked else None
    pool_ref = next(refs)
    seed_refs = [next(refs) for _ in range(3 * seeded)]
    o_ref, buf, sems, state, m_s, l_s, acc_s, *seed_scratch = refs
    b, n_rows = pl.program_id(0), pl.num_programs(0)
    length = lengths_ref[b]
    chunk_keys = chunk_blocks * block_size

    fetches = partial(
        _chunk_copies, tables_ref, pool_ref, buf, sems, block_size=block_size,
        chunk_blocks=chunk_blocks, table_width=table_width,
    )

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
        # the chunk the row's walk starts at: past the run a shared pass
        # walked for it, whose statistics it then goes on from
        chunk0 = first_ref[b] if seeded else 0

        @pl.when(state[1] == 0)
        def _():
            start(b, chunk0, first)

        def fresh():
            m_s[...] = jnp.full_like(m_s, NEG_INF)
            l_s[...] = jnp.zeros_like(l_s)
            acc_s[...] = jnp.zeros_like(acc_s)

        if seeded:
            pl.when(chunk0 == 0)(fresh)

            @pl.when(chunk0 > 0)
            def _():
                ml_s, seed_sem = seed_scratch
                seeds = [
                    pltpu.make_async_copy(src.at[place_ref[b]], dst, seed_sem)
                    for src, dst in zip(
                        seed_refs, (ml_s.at[0], ml_s.at[1], acc_s)
                    )
                ]
                for copy in seeds:
                    copy.start()
                for copy in seeds:
                    copy.wait()
                m_s[...] = ml_s[0, :, :1]
                l_s[...] = ml_s[1, :, :1]
        else:
            fresh()
        q = q_ref[...]

        def chunk_step(i, slot):
            other = 1 - slot

            @pl.when(i + 1 < n_chunks)
            def _():
                start(b, i + 1, other)

            @pl.when((i + 1 == n_chunks) & (nxt < n_rows))
            def _():
                start(nxt, first_ref[nxt] if seeded else 0, other)

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
            # a chunk that is walked holds at least one key under the
            # length, so the new maximum is a real score and a masked
            # key's weight underflows to exactly 0 (under a ``keep`` mask a
            # chunk may hold none that is kept: its weights are set to 0)
            _fold_chunk(s, rows, m_s, l_s, acc_s, seen if masked else None)
            return other

        state[0] = jax.lax.fori_loop(chunk0, n_chunks, chunk_step, first)
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
    return _walk_rows(
        q_row, pool, block_table, lengths, starts, keep, None, scale=scale,
        d_out=d_out, chunk_blocks=chunk_blocks, interpret=interpret,
    )


def _walk_rows(
    q_row, pool, block_table, lengths, starts, keep, seeds, *, scale, d_out,
    chunk_blocks, interpret,
):
    """The call of :func:`_decode_kernel`.  ``seeds``: ``(first_chunk,
    place, next_live [B], m, l [places, H, 128], acc [places, H, d_out])``
    of a plan and its shared pass, for rows that go on from it
    (:func:`shared_run_decode_attention`); None: every row starts at its
    first chunk from nothing."""
    b, h, w = q_row.shape
    _, block_size, _ = pool.shape
    m = block_table.shape[1]
    lengths = lengths.astype(jnp.int32)
    if seeds is None:
        next_live, carried = next_live_slot(lengths), ()
    else:
        first_chunk, place, next_live, *carried = seeds

    def row(i, *_):
        return (i, 0, 0)

    bounded, masked, seeded = (
        starts is not None, keep is not None, seeds is not None
    )
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
    inputs += (pool,)
    in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
    scratch = [
        pltpu.VMEM((2, chunk_blocks * block_size, w), pool.dtype),
        pltpu.SemaphoreType.DMA((2,)),
        pltpu.SMEM((2,), jnp.int32),
        pltpu.VMEM((h, 1), jnp.float32),
        pltpu.VMEM((h, 1), jnp.float32),
        pltpu.VMEM((h, d_out), jnp.float32),
    ]
    flags = {"masked": True} if masked else {}
    if seeded:
        prefetch += (first_chunk, place)
        inputs += tuple(carried)
        in_specs += [pl.BlockSpec(memory_space=pl.ANY)] * len(carried)
        scratch += [
            pltpu.VMEM((2, h, _STAT_LANES), jnp.float32),
            pltpu.SemaphoreType.DMA(()),
        ]
        flags["seeded"] = True
    return pl.pallas_call(
        partial(
            _decode_kernel, scale=scale, block_size=block_size,
            chunk_blocks=chunk_blocks, table_width=m, bounded=bounded,
            **flags,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(b,),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((None, h, d_out), row),
            scratch_shapes=scratch,
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, d_out), pool.dtype),
        # the buffer in flight is handed from one slot's program to the next
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)
        ),
        interpret=interpret,
    )(*prefetch, *inputs)


# rows of a tile: whose queries one shared pass stacks.  As stored, a
# stacked row of ``[v, k]`` rows costs the MXU as much as a row read alone
# costs the HBM from about the fourth on, so a wider tile buys nothing
TILE_ROWS = 8
# what the shared pass may hold in VMEM: two chunks of the pool, the
# tile's queries, scores and accumulator (17 MB at 48 heads x 2,048 lanes)
# against a default of 16 MiB; the v5e has 128 MiB
SHARED_VMEM_BYTES = 48 << 20


def _plan_kernel(
    lengths_ref, tables_ref, rows_ref, lead_ref, run_ref, next_run_ref,
    first_ref, place_ref, next_live_ref, *, block_size, chunk_blocks,
    tile_rows,
):
    """:func:`shared_run_plan` on the scalar core: ``lengths`` [B] and the
    flattened tables in SMEM; every result an int32 array in SMEM."""
    b = lengths_ref.shape[0]
    m = tables_ref.shape[0] // b
    r, places = tile_rows, rows_ref.shape[0]
    i32 = jnp.int32

    def loop(lo, hi, body, init=None):
        return jax.lax.fori_loop(lo, hi, body, i32(0) if init is None else init)

    def first_entry(i):
        return tables_ref[i * m]

    # the order: live rows first as the slots are, idle rows after them
    n_live = loop(0, b, lambda i, n: n + (lengths_ref[i] > 0).astype(i32))

    def seat(i, seats):
        live = lengths_ref[i] > 0
        at = jnp.where(live, seats[0], seats[1])
        rows_ref[at] = i
        first_ref[i] = 0
        return seats[0] + live.astype(i32), seats[1] + 1 - live.astype(i32)

    loop(0, b, seat, (i32(0), n_live))
    for p in range(b, places):  # past the batch: the last slot again
        rows_ref[p] = b - 1

    # ... and the live rows by their first entry, each sunk past the rows
    # before it whose entry is larger (stable: equal entries keep their order)
    def sink(p, _):
        row = rows_ref[p]

        def ahead(q):
            return (q > 0) & (
                first_entry(rows_ref[jnp.maximum(q - 1, 0)]) > first_entry(row)
            )

        def shift(q):
            rows_ref[q] = rows_ref[q - 1]
            return q - 1

        rows_ref[jax.lax.while_loop(ahead, shift, p)] = row
        return 0

    loop(1, n_live, sink)

    def note(p, _):
        place_ref[rows_ref[p]] = p
        return 0

    loop(0, b, note)

    def tile(t, _):
        lo = t * r
        n = jnp.clip(n_live - lo, 0, r)  # the tile's live rows lead it

        def vote(a, best):  # (votes, lead): the first row with the most
            entry = first_entry(rows_ref[lo + a])
            votes = loop(0, n, lambda c, v: v + (
                first_entry(rows_ref[lo + c]) == entry
            ).astype(i32))
            more = votes > best[0]
            return (
                jnp.where(more, votes, best[0]),
                jnp.where(more, rows_ref[lo + a], best[1]),
            )

        _, lead = loop(0, n, vote, (i32(0), rows_ref[lo]))

        def shares(a, found):  # (members, the least they share, in chunks)
            row = rows_ref[lo + a]
            whole = jnp.minimum((lengths_ref[row] - 1) // block_size, m)
            common = jax.lax.while_loop(
                lambda k: (k < whole)
                & (tables_ref[row * m + jnp.minimum(k, m - 1)]
                   == tables_ref[lead * m + jnp.minimum(k, m - 1)]),
                lambda k: k + 1, i32(0),
            )
            chunks = common // chunk_blocks
            first_ref[row] = (chunks > 0).astype(i32)  # a member, for now
            return (
                found[0] + (chunks > 0).astype(i32),
                jnp.where(chunks > 0, jnp.minimum(found[1], chunks), found[1]),
            )

        members, least = loop(
            0, n, shares, (i32(0), i32(jnp.iinfo(jnp.int32).max))
        )
        run = jnp.where(members >= 2, least, 0)

        def start(a, _):
            row = rows_ref[lo + a]
            first_ref[row] = first_ref[row] * run
            return 0

        loop(0, n, start)
        lead_ref[t] = lead
        run_ref[t] = run
        return 0

    tiles = places // r
    loop(0, tiles, tile)

    # the next tile with a run and the next slot with a length, from the end
    def back(src, dst, count):
        def step(k, nxt):
            at = count - 1 - k
            dst[at] = nxt
            return jnp.where(src[at] > 0, at, nxt)

        loop(0, count, step, i32(count))

    back(run_ref, next_run_ref, tiles)
    back(lengths_ref, next_live_ref, b)


def shared_run_plan(block_table, lengths, *, block_size, chunk_blocks,
                    tile_rows):
    """Which leading table entries the rows of a decode step have in
    common, from the block table and the lengths alone (ONE small kernel
    on the scalar core: a few microseconds, and one operation of the
    program that calls it).

    The rows are ordered so that live ones come first and rows with the
    same first table entry lie together (a stable sort), and the order is
    cut into TILES of ``tile_rows``.  A tile's LEAD is the live row whose
    first entry most of its live rows have (the first such in the order:
    a stray row of another prefix at a tile's head does not cost the rest
    their run).  A MEMBER is a live row whose leading entries equal the
    lead's for at least one chunk of ``chunk_blocks`` entries, all of them
    whole blocks strictly under its length (a row writes its last block in
    this very step; a shared block is never that one, and every key of it
    is under the length); the RUN is the least such count among the
    members in whole chunks, and 0 with fewer than two members.  A live
    row that is no member (another prefix, a private copy of the same
    tokens) takes no part.

    Returns a dict of int32 arrays: ``rows`` [tiles * tile_rows], the
    slots in tile order (past the batch: the last slot again); ``lead``,
    ``run`` [tiles], each tile's lead (a slot) and run in chunks;
    ``next_run`` [tiles], the next tile with a run (``tiles``: none);
    ``first_chunk`` [B], the chunk each slot's own walk starts at (its
    tile's run for a member, else 0); ``place`` [B], each slot's place in
    the order; ``next_live`` [B], :func:`next_live_slot`."""
    return _plan(
        block_table, lengths, block_size=block_size,
        chunk_blocks=chunk_blocks, tile_rows=tile_rows,
        interpret=backend.pallas_interpret(),
    )


# jitted under ONE name, so that the layers' calls and the counter's are
# the same operation of a step's program, and the compiler keeps one
@partial(
    jax.jit,
    static_argnames=("block_size", "chunk_blocks", "tile_rows", "interpret"),
)
def _plan(block_table, lengths, *, block_size, chunk_blocks, tile_rows,
          interpret):
    b = block_table.shape[0]
    tiles = -(-b // tile_rows)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    names = ("rows", "lead", "run", "next_run", "first_chunk", "place",
             "next_live")
    sizes = (tiles * tile_rows, tiles, tiles, tiles, b, b, b)
    out = pl.pallas_call(
        partial(
            _plan_kernel, block_size=block_size, chunk_blocks=chunk_blocks,
            tile_rows=tile_rows,
        ),
        in_specs=[smem, smem],
        out_specs=[smem] * len(names),
        out_shape=[jax.ShapeDtypeStruct((n,), jnp.int32) for n in sizes],
        interpret=interpret,
    )(lengths.astype(jnp.int32), block_table.reshape(-1).astype(jnp.int32))
    return dict(zip(names, out))


def shared_run_rows_fetched(block_table, lengths, *, block_size):
    """Cached rows :func:`shared_run_decode_attention` fetches in a decode
    step (int32 scalar): a tile's run once, and of each live row the whole
    blocks from the chunk its own walk starts at to its length."""
    b, m = block_table.shape
    chunk_blocks = min(CHUNK_BLOCKS, m)
    plan = shared_run_plan(
        block_table, lengths, block_size=block_size,
        chunk_blocks=chunk_blocks, tile_rows=min(TILE_ROWS, b),
    )
    own = -(-lengths.astype(jnp.int32) // block_size) - (
        plan["first_chunk"] * chunk_blocks
    )
    return (
        (jnp.sum(plan["run"]) * chunk_blocks + jnp.sum(own)) * block_size
    ).astype(jnp.int32)


def _shared_kernel(
    run_ref, lead_ref, rows_ref, tables_ref, next_ref, q_ref, pool_ref,
    m_ref, l_ref, acc_ref, q_s, ml_s, buf, sems, io_sem, state, m_s, l_s, acc_s, *, scale,
    block_size, chunk_blocks, table_width, tile_rows, q_from,
):
    """One tile's shared pass.  Scalar prefetch: ``run`` [tiles] (chunks),
    ``lead`` [tiles] (the slot whose table is walked), ``rows`` [tiles *
    tile_rows] (slots in tile order), the flattened
    tables, ``next`` [tiles] (the next tile with a run).  ``q`` [B, H, W]
    and the pool in HBM; ``m``, ``l`` [tiles, tile_rows * H, 128] (the
    running maximum and the normaliser, each across a tile of lanes: a
    copy of one lane is none Mosaic makes) and ``acc`` [tiles, tile_rows * H, d_out] in
    HBM, written for a tile with a run and left alone otherwise.  Scratch:
    the tile's queries ``q_s`` [tile_rows * H, W] and ``ml_s``, from where
    the two leave, then :func:`_decode_kernel`'s (and a semaphore for the
    queries and the results)."""
    t, n_tiles = pl.program_id(0), pl.num_programs(0)
    run = run_ref[t]
    h = q_ref.shape[1]
    d_out = acc_s.shape[-1]

    def fetches(tile, chunk, slot):
        return _chunk_copies(
            tables_ref, pool_ref, buf, sems, lead_ref[tile], chunk, slot,
            block_size=block_size, chunk_blocks=chunk_blocks,
            table_width=table_width,
        )

    def start(tile, chunk, slot):
        for copy in fetches(tile, chunk, slot):
            copy.start()

    def run_all(copies):
        for copy in copies:
            copy.start()
        for copy in copies:
            copy.wait()

    @pl.when(t == 0)
    def _():
        state[0] = 0
        state[1] = 0

    @pl.when(run > 0)
    def _():
        nxt = next_ref[t]
        first = state[0]

        @pl.when(state[1] == 0)
        def _():
            start(t, 0, first)

        # every row of the tile, member or not: a product's rows do not mix
        run_all([
            pltpu.make_async_copy(
                q_ref.at[rows_ref[t * tile_rows + i]],
                q_s.at[pl.ds(i * h, h)], io_sem,
            )
            for i in range(tile_rows)
        ])
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)
        q = q_s[:, q_from:]

        def chunk_step(i, slot):
            other = 1 - slot

            @pl.when(i + 1 < run)
            def _():
                start(t, i + 1, other)

            @pl.when((i + 1 == run) & (nxt < n_tiles))
            def _():
                start(nxt, 0, other)

            for copy in fetches(t, i, slot):
                copy.wait()
            # no mask: every key of the run lies under every member's length
            s = jax.lax.dot_general(
                q, buf[slot, :, q_from:], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale
            _fold_chunk(s, buf[slot, :, :d_out], m_s, l_s, acc_s)
            return other

        state[0] = jax.lax.fori_loop(0, run, chunk_step, first)
        state[1] = (nxt < n_tiles).astype(jnp.int32)
        ml_s[0] = jnp.broadcast_to(m_s[...], ml_s.shape[1:])
        ml_s[1] = jnp.broadcast_to(l_s[...], ml_s.shape[1:])
        run_all([
            pltpu.make_async_copy(src, dst.at[t], io_sem)
            for src, dst in (
                (ml_s.at[0], m_ref), (ml_s.at[1], l_ref), (acc_s, acc_ref)
            )
        ])


def shared_run_decode_attention(
    q_row: jnp.ndarray,  # [B, H, W], the pool's dtype
    pool: jnp.ndarray,  # [N_blocks, block_size, W]
    block_table: jnp.ndarray,  # [B, M] int32 pool block ids
    lengths: jnp.ndarray,  # [B] int32 keys each row attends; 0: none
    *,
    scale: float,
    d_out: int,
    q_from: int = 0,  # lanes before it are zero in every query
) -> jnp.ndarray:
    """:func:`latent_decode_attention` without ``starts`` or ``keep``, for
    rows whose tables may open with the SAME blocks (a prefix the cache
    holds once): those blocks are read once a tile of ``TILE_ROWS`` rows
    instead of once a row.

    Two passes under one running softmax (:func:`shared_run_plan` says
    which rows and how far, from the table and the lengths alone).  The
    SHARED pass, a grid step a tile: the tile's queries stacked as the
    rows of one product walk the run's chunks through the first row's
    table, with no mask, and leave every (row, head) its running maximum,
    normaliser and float32 accumulator; a tile with no run does nothing.
    The OWN pass is :func:`latent_decode_attention`'s kernel, in which a
    member starts at the chunk after the run from what the shared pass
    left it, and every other row at its first chunk from nothing.  A row
    meets its chunks in the order it always did, so with no run anywhere
    the result is :func:`latent_decode_attention`'s bit for bit.
    ``q_from``: the score product skips the lanes before it (a whole
    number of 128-lane tiles), which the caller vouches are zero in every
    query; the own pass multiplies them as it always did."""
    b, m = q_row.shape[0], block_table.shape[1]
    return _attend_shared_run(
        q_row, pool, block_table, lengths, scale=float(scale), d_out=d_out,
        chunk_blocks=min(CHUNK_BLOCKS, m), tile_rows=min(TILE_ROWS, b),
        q_from=q_from, interpret=backend.pallas_interpret(),
    )


@partial(
    jax.jit,
    static_argnames=(
        "scale", "d_out", "chunk_blocks", "tile_rows", "q_from", "interpret",
    ),
)
def _attend_shared_run(
    q_row, pool, block_table, lengths, *, scale, d_out, chunk_blocks,
    tile_rows, q_from, interpret,
):
    b, h, w = q_row.shape
    _, block_size, _ = pool.shape
    m = block_table.shape[1]
    tiles = -(-b // tile_rows)
    plan = _plan(
        block_table, lengths, block_size=block_size,
        chunk_blocks=chunk_blocks, tile_rows=tile_rows, interpret=interpret,
    )
    tables = block_table.reshape(-1).astype(jnp.int32)
    stacked = tile_rows * h
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    m_run, l_run, acc_run = pl.pallas_call(
        partial(
            _shared_kernel, scale=scale, block_size=block_size,
            chunk_blocks=chunk_blocks, table_width=m, tile_rows=tile_rows,
            q_from=q_from,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(tiles,),
            in_specs=[hbm, hbm],
            out_specs=[hbm, hbm, hbm],
            scratch_shapes=[
                pltpu.VMEM((stacked, w), q_row.dtype),
                pltpu.VMEM((2, stacked, _STAT_LANES), jnp.float32),
                pltpu.VMEM((2, chunk_blocks * block_size, w), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA(()),
                pltpu.SMEM((2,), jnp.int32),
                pltpu.VMEM((stacked, 1), jnp.float32),
                pltpu.VMEM((stacked, 1), jnp.float32),
                pltpu.VMEM((stacked, d_out), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((tiles, stacked, _STAT_LANES), jnp.float32),
            jax.ShapeDtypeStruct((tiles, stacked, _STAT_LANES), jnp.float32),
            jax.ShapeDtypeStruct((tiles, stacked, d_out), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=SHARED_VMEM_BYTES,
        ),
        interpret=interpret,
    )(
        plan["run"], plan["lead"], plan["rows"], tables, plan["next_run"],
        q_row, pool,
    )
    places = tiles * tile_rows
    seeds = (
        plan["first_chunk"], plan["place"], plan["next_live"],
        m_run.reshape(places, h, _STAT_LANES),
        l_run.reshape(places, h, _STAT_LANES),
        acc_run.reshape(places, h, d_out),
    )
    return _walk_rows(
        q_row, pool, block_table, lengths, None, None, seeds, scale=scale,
        d_out=d_out, chunk_blocks=chunk_blocks, interpret=interpret,
    )
