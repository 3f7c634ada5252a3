"""Scaled dot-product / multi-head attention.

NOT in the reference — VELES/Znicz predates transformers (SURVEY.md 5.7) —
but the rebuild treats long-context as first-class: this is the single-device
reference implementation that :mod:`znicz_tpu.parallel.ring_attention`
shards over the mesh's sequence axis.

Layouts: ``q/k/v`` are ``[batch, seq, heads, head_dim]`` (BTHD).
"""

from __future__ import annotations

from functools import partial
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from znicz_tpu import observability
from znicz_tpu.core import backend, prng
from znicz_tpu.ops.filling import fill
from znicz_tpu.ops.pallas import kept_rows_attention
from znicz_tpu.ops.pallas.latent_attention import (
    latent_decode_attention,
    shared_run_decode_attention,
    shared_run_rows_fetched,
)
from znicz_tpu.ops.pallas.sparse_index import index_decode_scores


def dot_product_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = False,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """Stable softmax attention; returns [B, Tq, H, D]."""
    if scale is None:
        scale = 1.0 / np.sqrt(q.shape[-1])
    s = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    if causal:
        tq, tk = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((tq, tk), bool), k=tk - tq)
        s = jnp.where(mask, s, -jnp.inf)
    p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    p = p / jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum(
        "bhqk,bkhd->bqhd", p.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    return out.astype(q.dtype)


def paged_attention(
    q: jnp.ndarray,  # [B, Tq, H, D]
    k_pool: jnp.ndarray,  # [N_blocks, block_size, H*D]
    v_pool: jnp.ndarray,
    block_table: jnp.ndarray,  # [B, M] int32 pool block ids
    q_pos: jnp.ndarray,  # [B, Tq] int32 absolute query positions
    *,
    block_size: int,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """Block-table attention over paged KV pools; returns [B, Tq, H, D].

    The serving KV layout (vLLM/PagedAttention lineage): K/V live in a
    shared ``[n_blocks, block_size, H*D]`` pool and each row owns an
    ordered block table — table entry ``j`` covers absolute positions
    ``j*block_size .. (j+1)*block_size-1`` of that row.  The row's
    window is GATHERED from the pool (``k_pool[block_table]``), so the
    compiled program is shape-static in everything but the traced table
    values: rows growing into new blocks, block reuse after retirement,
    and any pool size never recompile.  ALIASING is first-class: many
    tables may reference the same physical block (prefix sharing — the
    engine refcounts and COW-splits before any write), the gather reads
    it once per referencing row, and validity stays PER-ROW — a shared
    block's positions past one row's ``q_pos`` are masked for that row
    even while a deeper row genuinely attends them (aliasing tests in
    tests/test_attention.py).

    Heads are MERGED in storage and the gathered window is read as it
    is stored; they are split on the QUERY side, after the gather.  A
    minor ``[block_size, H*D]`` fills the TPU's (8, 128) tiles, where
    ``[H, D]`` fits none: with heads split in the pool the compiler
    re-tiled every pool and every window in every step, and with heads
    split in the gathered window it still re-tiled the window
    (tests/test_paged_layout_aot.py; PERF.md section 6, PR 25).  So
    row ``(t, h)`` of ``q_heads`` holds ``q[b, t, h]`` in head ``h``'s
    columns of the merged width and zeros elsewhere, and ONE product
    against the merged window gives every head's scores; the weighted
    sum runs over the merged width too, and row ``(t, h)`` keeps head
    ``h``'s columns of it.  Both products run at ``Precision.HIGHEST``,
    in f32 like the window: a decode step's always were (at ``Tq`` = 1
    the compiler made the per-head products f32 multiply-reduce loops),
    the zeros then add exactly, and at one bf16 pass the compiler would
    also round the window to bf16 as the gather writes it.  The zeros
    cost H times the products' FLOPs.  On the v5e (PERF.md section 6,
    PR 25; 12 x 64, 16 x 128 and 32 x 128 heads) that is 1.7 to 2.9
    times faster in a decode step than the per-head einsum over a window
    with the heads split, level with it while ``Tq * H`` is at most 512
    (a verify call of 4 or a 32-token prefill chunk, to 16 heads), and
    slower beyond: 1.3 times in a verify call of 4 at 32 heads, 3 times
    in a 32-token prefill chunk at 32 heads.  A pool that keeps the
    heads split (``[.., H, D]``) reads the same.

    Validity is by ABSOLUTE key index, exactly like the dense cache
    path (:mod:`znicz_tpu.workflow.generate`): key position must be
    ``<= q_pos``, so unallocated or stale table entries — whose
    positions fall outside every valid window — are masked out by
    INDEX, never read through.  Numerics mirror
    :func:`dot_product_attention`: f32 score accumulation, stable
    softmax, f32 value accumulation.
    """
    if scale is None:
        # a static shape's root, taken once at trace time on purpose
        scale = 1.0 / np.sqrt(q.shape[-1])  # znicz-check: disable=ZNC002
    b, tq, h, d = q.shape
    n_keys = block_table.shape[1] * block_size
    # [B, M, bs, H*D] -> [B, M*bs, H*D]: the row-ordered KV window
    k = k_pool[block_table].reshape(b, n_keys, h * d)
    v = v_pool[block_table].reshape(b, n_keys, h * d)
    # [B, Tq, H, H', D] -> [B, Tq*H, H'*D], zero where H' != H
    q_heads = (
        q[:, :, :, None, :] * jnp.eye(h, dtype=q.dtype)[:, :, None]
    ).reshape(b, tq * h, h * d)
    s = jnp.einsum(
        "bre,bke->brk", q_heads, k, precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    ).reshape(b, tq, h, n_keys) * scale
    k_idx = jnp.arange(n_keys)[None, None, None, :]
    s = jnp.where(k_idx <= q_pos[:, :, None, None], s, -jnp.inf)
    p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    p = p / jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum(
        "brk,bke->bre", p.astype(v.dtype).reshape(b, tq * h, n_keys), v,
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )
    # row (t, h) keeps head h's own columns
    out = jnp.einsum("bthhd->bthd", out.reshape(b, tq, h, h, d))
    return out.astype(q.dtype)


def gqa_cache_row(k: jnp.ndarray, v: jnp.ndarray) -> jnp.ndarray:
    """The row :func:`paged_gqa_attention` caches for one token of one
    layer: ``[v (G * D), k (G * D)]`` from ``k``, ``v`` [..., G, D]."""
    lead = k.shape[:-2]
    return jnp.concatenate(
        [v.reshape(lead + (-1,)), k.reshape(lead + (-1,))], axis=-1
    )


def ring_key_positions(
    width: int, block_size: int, q_pos: jnp.ndarray
) -> jnp.ndarray:
    """Absolute positions [B, width * block_size] of the keys a RING
    table of ``width`` entries names, for rows whose queries lie in block
    ``q_pos // block_size``: entry ``j`` holds the newest block ``b`` of
    the row with ``b % width == j`` and ``b`` not past the queries' own.
    An entry no block has reached yet comes out negative."""
    q_blk = (q_pos // block_size)[:, None]
    entry = jnp.arange(width)[None, :]
    blk = q_blk - (q_blk - entry) % width
    pos = blk[:, :, None] * block_size + jnp.arange(block_size)[None, None, :]
    return pos.reshape(q_pos.shape[0], width * block_size)


def paged_gqa_attention(
    q: jnp.ndarray,  # [B, Tq, H, D]
    pool: jnp.ndarray,  # [N_blocks, block_size, 2 * G * D]: [v, k] a token
    block_table: jnp.ndarray,  # [B, M] int32 pool block ids
    q_pos: jnp.ndarray,  # [B, Tq] int32 absolute query positions
    *,
    block_size: int,
    n_kv_heads: int,
    window: Optional[int] = None,
    lengths: Optional[jnp.ndarray] = None,  # [B] int32; 0: the row idles
    scale: Optional[float] = None,
    grouped_prefill: bool = False,
) -> jnp.ndarray:
    """GROUPED-QUERY attention over a paged pool: ``H`` query heads read
    ``G = n_kv_heads`` cached K/V heads, query head ``h`` the head ``h //
    (H / G)``; returns [B, Tq, H * D] float32.

    A cached token is ONE row ``[v (G * D), k (G * D)]``
    (:func:`gqa_cache_row`): key and value are one fetch, and at ``G * D``
    = 512 each half is whole 128-lane tiles (the layout rule of
    :func:`paged_attention`).  Queries are laid out as there: row ``(t,
    h)`` holds ``q[b, t, h]`` in the key columns of ITS K/V head and zeros
    elsewhere, so one product against the rows as stored gives every
    head's scores (the zeros add exactly), the weighted sum runs over the
    value half, and row ``(t, h)`` keeps its head's columns of it.  That
    is the form :func:`~znicz_tpu.ops.pallas.latent_attention
    .latent_decode_attention` computes, so on the TPU a decode step
    (``Tq`` 1) reads the pool IN PLACE through it, as far as each row's
    length (a ring is first turned so that the window's first block
    leads, :func:`_window_in_table_order`, and the kernel is told where
    in that block the window starts); everything else gathers ``pool
    [block_table]`` at the table's width and computes on the copy.  A
    plain table's in-place step is :func:`~znicz_tpu.ops.pallas.latent
    _attention.shared_run_decode_attention`: the leading blocks that the
    live rows of a tile of 8 have IN COMMON (a prefix the cache holds
    once) are read once for the tile, the rows' queries stacked, and each
    row then reads only the blocks that are its own, under the same
    running softmax; rows that share nothing are read as before, bit for
    bit.  A ring's first block differs a row, so a window layer keeps the
    one pass.
    ``grouped_prefill``: a call of SEVERAL queries a row walks the table
    instead, as far as its last query, under a running softmax with the
    products grouped a K/V head (:func:`_kept_rows_walk` under the causal
    mask, :func:`kept_gqa_attention`'s prefill form): for a tower whose
    ``Tq * H`` query rows (8,192 at 64 heads) against the table's width
    would make the as-stored layout's zeros ``G`` times the FLOPs and its
    scores a gigabyte.

    Validity is by ABSOLUTE key index.  ``window`` None: the table is
    plain (entry ``j`` covers positions ``j * block_size ..``) and key
    ``k`` is visible iff ``k <= q_pos``.  ``window`` W: the table is a
    RING (:func:`ring_key_positions`; a call's queries of one row lie in
    one block) and key ``k`` is visible iff ``q_pos - W < k <= q_pos``:
    the last ``W`` keys, the query's own among them.  ``lengths`` is a
    decode step's: 0 marks a row that idles, whose result is zeros.
    Products take the pool's dtype and accumulate in float32; scores and
    softmax are float32.
    """
    with jax.named_scope("attn_global" if window is None else "attn_window"):
        return _paged_gqa_attention(
            q, pool, block_table, q_pos, block_size=block_size,
            n_kv_heads=n_kv_heads, window=window, lengths=lengths,
            scale=1.0 / np.sqrt(q.shape[-1]) if scale is None else scale,
            grouped_prefill=grouped_prefill,
        )


def _window_in_table_order(block_table, lengths, *, block_size, window):
    """A decode step's view of a RING table for the kernel that walks a
    table from its first entry: ``(table, lengths, starts)`` with the
    ring turned so that the block holding the window's first key leads,
    ``lengths`` [B] counted from that block's first row (0 stays 0) and
    ``starts`` [B] the window's first key within it.  ``lengths`` coming
    in is ``position + 1``."""
    width = block_table.shape[1]
    first_key = jnp.maximum(lengths - window, 0)
    first_blk = first_key // block_size
    turned = jnp.take_along_axis(
        block_table, (first_blk[:, None] + jnp.arange(width)[None, :]) % width,
        axis=1,
    )
    base = first_blk * block_size
    return turned, jnp.maximum(lengths - base, 0), first_key - base


def paged_gqa_rows_read(
    block_table: jnp.ndarray,  # [B, M]
    lengths: jnp.ndarray,  # [B] int32: position + 1; 0: the row idles
    *,
    block_size: int,
    window: Optional[int] = None,
) -> jnp.ndarray:
    """Cached rows ONE layer's grouped-query attention FETCHES in a decode
    step (int32 scalar), by the form that runs here: gathered, every
    slot's table; in place behind a window, each row's keys from the first
    block it attends, rounded up to whole blocks; in place over a plain
    table, the blocks a tile of rows have in common ONCE and each row's
    own (:func:`~znicz_tpu.ops.pallas.latent_attention.shared_run_rows
    _fetched`).  What the rows ATTEND is :func:`paged_gqa_rows_attended`:
    the two differ by the rows read once for several."""
    if window is None and _reads_pool_in_place(1):
        return shared_run_rows_fetched(
            block_table, lengths, block_size=block_size
        )
    return paged_gqa_rows_attended(
        block_table, lengths, block_size=block_size, window=window
    )


def paged_gqa_rows_attended(
    block_table: jnp.ndarray,  # [B, M]
    lengths: jnp.ndarray,  # [B] int32: position + 1; 0: the row idles
    *,
    block_size: int,
    window: Optional[int] = None,
) -> jnp.ndarray:
    """Cached rows the queries of ONE layer's grouped-query attention meet
    in a decode step (int32 scalar), a row counted once for EACH query
    that meets it: in place, each row's keys from the first block it
    attends, rounded up to whole blocks; gathered, every slot's table.
    :func:`paged_gqa_rows_read` over this is the share of that traffic
    still fetched."""
    if not _reads_pool_in_place(1):
        return jnp.int32(block_table.size * block_size)
    if window is not None:
        _, lengths, _ = _window_in_table_order(
            block_table, lengths, block_size=block_size, window=window
        )
    return jnp.sum(-(-lengths // block_size) * block_size, dtype=jnp.int32)


def _paged_gqa_attention(
    q, pool, block_table, q_pos, *, block_size, n_kv_heads, window, lengths,
    scale, grouped_prefill=False,
):
    b, tq, h, d = q.shape
    g, half = n_kv_heads, n_kv_heads * d
    dtype = pool.dtype
    f32 = dict(preferred_element_type=jnp.float32)
    if pool.shape[-1] != 2 * half:
        raise ValueError(
            f"a cached row is [v, k] of {n_kv_heads} heads x {d}: want "
            f"{2 * half} lanes, the pool has {pool.shape[-1]}"
        )
    if lengths is not None and tq != 1:
        raise ValueError(
            f"lengths are a decode step's; got {tq} queries a row"
        )
    if grouped_prefill and tq > 1:
        n_keys = block_table.shape[1] * block_size
        if window is None:
            k_pos, last = jnp.arange(n_keys)[None, :], q_pos[:, -1]
        else:  # a ring: any entry may hold keys of the window
            k_pos = ring_key_positions(
                block_table.shape[1], block_size, q_pos[:, -1]
            )
            last = jnp.full((b,), n_keys - 1, jnp.int32)
        k_pos, at = k_pos[:, None, :], q_pos[:, :, None]
        keep = (k_pos <= at) & (k_pos >= 0)
        if window is not None:
            keep = keep & (k_pos > at - window)
        scores, weighted = _grouped_products(q, g, dtype, scale)
        return _kept_rows_walk(
            pool, block_table, keep, last, (b, tq, h), block_size=block_size,
            out_width=d, scores=scores, weighted=weighted,
        ).reshape(b, tq, h * d)
    q_row = _gqa_query_rows(q, g, dtype)
    if _reads_pool_in_place(tq):
        table, keys, starts = block_table, lengths, None
        if keys is None:
            keys = q_pos[:, 0] + 1
        if window is not None:
            table, keys, starts = _window_in_table_order(
                table, keys, block_size=block_size, window=window
            )
        # the kernel wants whole (16, 128) tiles of query rows
        q_row = jnp.pad(q_row, ((0, 0), (0, -h % 16), (0, 0)))
        if window is None:  # rows may open with the same blocks: read once
            o = shared_run_decode_attention(
                q_row, pool, table, keys, scale=scale, d_out=half,
                # the value half of a query row is zeros: where rows are
                # stacked the score product is the MXU's, and skips it
                q_from=half if half % 128 == 0 else 0,
            )
        else:
            o = latent_decode_attention(
                q_row, pool, table, keys, scale=scale, d_out=half,
                starts=starts,
            )
        o = o[:, :h].astype(jnp.float32)
    else:
        n_keys = block_table.shape[1] * block_size
        rows = pool[block_table].reshape(b, n_keys, 2 * half)
        if window is None:
            k_pos = jnp.arange(n_keys)[None, :]
        else:
            k_pos = ring_key_positions(
                block_table.shape[1], block_size, q_pos[:, -1]
            )
        k_pos, at = k_pos[:, None, None, :], q_pos[:, :, None, None]
        valid = (k_pos <= at) & (k_pos >= 0)
        if window is not None:
            valid = valid & (k_pos > at - window)
        s = jnp.einsum("bre,bke->brk", q_row, rows, **f32)
        s = jnp.where(valid, s.reshape(b, tq, h, n_keys) * scale, -jnp.inf)
        p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
        p = (p / jnp.sum(p, axis=-1, keepdims=True)).astype(dtype)
        # over the whole row: a slice of the gathered rows is a copy of
        # them, and the compiler turns a slice of this result into one
        o = jnp.einsum(
            "brk,bke->bre", p.reshape(b, tq * h, n_keys), rows, **f32
        )
    o = _gqa_own_values(o.reshape(b, tq, h, -1), g, d)
    if lengths is not None:  # an idle row's result is zeros in either form
        o = jnp.where(lengths[:, None, None] > 0, o, 0.0)
    return o


def _gqa_query_rows(q, g, dtype):
    """Queries [B, Tq, H, D] laid out like the ``[v, k]`` rows they meet:
    [B, Tq * H, 2 * G * D], row ``(t, h)`` holding ``q[b, t, h]`` in the key
    columns of ITS K/V head and zeros elsewhere."""
    b, tq, h, d = q.shape
    # [B, Tq, G, H/G, G', D] -> [B, Tq * H, G' * D], zero where G' != G
    q_keys = (
        q.astype(dtype).reshape(b, tq, g, h // g, 1, d)
        * jnp.eye(g, dtype=dtype)[:, None, :, None]
    ).reshape(b, tq * h, g * d)
    return jnp.concatenate([jnp.zeros_like(q_keys), q_keys], axis=-1)


def _gqa_own_values(o, g, d):
    """Of a weighted sum over ``[v, k]`` rows as stored, ``o`` [B, Tq, H,
    >= G * D], row ``(t, h)`` keeps the value columns of its own K/V head:
    [B, Tq, H * D] float32, picked by a product with 0 / 1 (exact at
    HIGHEST, and small) rather than sliced."""
    b, tq, h, _ = o.shape
    lane = jnp.arange(o.shape[-1])[None, :, None]
    own = (jnp.arange(h) // (h // g) * d)[:, None, None] + jnp.arange(d)
    return jnp.einsum(
        "bthe,hed->bthd", o.astype(jnp.float32),
        (lane == own).astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    ).reshape(b, tq, h * d)


def paged_latent_attention(
    q_nope: jnp.ndarray,  # [B, Tq, H, d_nope]
    q_rope: jnp.ndarray,  # [B, Tq, H, d_rope], rotated
    pool: jnp.ndarray,  # [N_blocks, block_size, >= d_latent + d_rope]
    block_table: jnp.ndarray,  # [B, M] int32 pool block ids
    q_pos: jnp.ndarray,  # [B, Tq] int32 absolute query positions
    wk_b: jnp.ndarray,  # [d_latent, H * d_nope]
    wv_b: jnp.ndarray,  # [d_latent, H * d_v]
    *,
    block_size: int,
    scale: float,
    absorbed: bool,
    lengths: Optional[jnp.ndarray] = None,  # [B] int32; 0: the row idles
) -> jnp.ndarray:
    """Multi-head LATENT attention over a paged pool of latent rows;
    returns [B, Tq, H * d_v] in float32.

    A cached token is ONE row ``[c (d_latent), rot(k_r) (d_rope), 0 ...]``
    (zeros up to the pool's width, which the caller rounds up to whole
    128-lane tiles: a 576-wide minor dimension fills none, and the TPU's
    compiler then stores the pool tokens-minor and every program copies
    every pool into the layout it computes in) shared by every head (DeepSeek-V2 lineage): head ``h``'s key is ``[c wk_b[:,
    h], rot(k_r)]`` and its value ``c wv_b[:, h]``.  A row finds its
    cached rows through the block table with :func:`paged_attention`'s
    contract (same aliasing, same validity by ABSOLUTE key index ``<=
    q_pos``, same f32 stable softmax), and the two forms give the same
    numbers:

    * ``absorbed=False`` MATERIALISES K and V of the whole window (``2 *
      window * d_latent * H * (d_nope + d_v)`` FLOPs, whatever ``Tq``) and
      attends per head: right for a prefill chunk, whose ``Tq * H``
      queries amortise it.
    * ``absorbed=True`` folds ``wk_b`` into the query (``q_abs = q_nope
      wk_b[h]^T``, ``d_latent`` wide) and ``wv_b`` into the output, so
      scores and the weighted sum run against the latent rows as stored:
      a decode step reads ``d_latent + d_rope`` values a cached token and
      never forms K or V.

    ``lengths`` says how many keys each row of a decode step (``Tq`` 1)
    attends: ``q_pos + 1`` where it is left out, 0 for a row that idles,
    whose result is zeros that nobody reads.

    Where the rows are read from.  The materialised form, and the
    absorbed form off the TPU, GATHER every row's window
    (``pool[block_table]``, the table's whole width for every slot, idle
    or not) and compute on the copy; the absorbed weighted sum then runs
    over the whole row and drops the tail of the small result, so the
    window is never sliced (a slice of it is a copy of it).  On the TPU a
    decode step's absorbed form is the Pallas kernel
    :func:`znicz_tpu.ops.pallas.latent_attention.latent_decode_attention`:
    the pool is read in place, block by block through the table, as far
    as each row's length; no window exists
    (:func:`paged_latent_rows_read` counts both).

    Products take the pool's dtype (bfloat16 in serving) and accumulate
    in float32.
    """
    with jax.named_scope("mla_absorbed" if absorbed else "mla_materialised"):
        return _paged_latent_attention(
            q_nope, q_rope, pool, block_table, q_pos, wk_b, wv_b,
            block_size=block_size, scale=scale, absorbed=absorbed,
            lengths=lengths,
        )


def _reads_pool_in_place(tq: int) -> bool:
    """Whether the absorbed form of ``tq`` queries a row is the kernel
    that reads the pool in place."""
    return tq == 1 and backend.on_tpu()


# registered with the module, so that the family is there for a server
# whose tower never selects
_KEPT_ROWS_FORM = observability.counter(
    "znicz_serve_kept_rows_attention_total",
    "layers of a decode program built to attend the cached rows a selection "
    "kept, by how the rows reach the two products",
    ("form",),
)


def _reads_kept_rows_in_place(tq: int, pool: jnp.ndarray) -> bool:
    """Whether attention of ``tq`` queries a row over the rows a selection
    kept is the kernel that fetches them from ``pool`` where it lies."""
    return _reads_pool_in_place(tq) and kept_rows_attention.fetchable(pool)


def paged_latent_rows_read(
    block_table: jnp.ndarray,  # [B, M]
    lengths: jnp.ndarray,  # [B] int32
    *,
    block_size: int,
) -> jnp.ndarray:
    """Cached rows ONE layer's absorbed attention reads in a decode step
    (int32 scalar), by the form that runs here: in place, each row's
    length rounded up to whole blocks; gathered, every slot's window."""
    if _reads_pool_in_place(1):
        return jnp.sum(-(-lengths // block_size) * block_size, dtype=jnp.int32)
    return jnp.int32(block_table.size * block_size)


def _window_softmax(s, q_pos, scale, dtype):
    """Stable softmax of scores [B, Tq, H, keys] over the keys at or
    before each query's position, rounded to ``dtype``."""
    keys = jnp.arange(s.shape[-1])[None, None, None, :]
    valid = keys <= q_pos[:, :, None, None]
    s = jnp.where(valid, s * scale, -jnp.inf)
    p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    return (p / jnp.sum(p, axis=-1, keepdims=True)).astype(dtype)


def _paged_latent_attention(
    q_nope, q_rope, pool, block_table, q_pos, wk_b, wv_b, *, block_size,
    scale, absorbed, lengths,
):
    b, tq, h, d_nope = q_nope.shape
    d_latent, d_rope = wk_b.shape[0], q_rope.shape[-1]
    n_keys = block_table.shape[1] * block_size
    dtype, width = pool.dtype, pool.shape[-1]
    f32 = dict(preferred_element_type=jnp.float32)
    if lengths is not None and tq != 1:
        raise ValueError(
            f"lengths are a decode step's; got {tq} queries a row"
        )
    if not absorbed:
        window = pool[block_table].reshape(b, n_keys, width)
        latent = window[..., :d_latent]
        k_rope = window[..., d_latent:d_latent + d_rope]
        k_nope = jnp.dot(latent, wk_b, **f32).astype(dtype)
        v = jnp.dot(latent, wv_b, **f32).astype(dtype)
        s = jnp.einsum(
            "bthn,bkhn->bthk", q_nope.astype(dtype),
            k_nope.reshape(b, n_keys, h, d_nope), **f32,
        ) + jnp.einsum("bthr,bkr->bthk", q_rope.astype(dtype), k_rope, **f32)
        p = _window_softmax(s, q_pos, scale, dtype)
        o = jnp.einsum(
            "bthk,bkhv->bthv", p, v.reshape(b, n_keys, h, -1), **f32
        )
        return o.reshape(b, tq, -1)
    q_abs = jnp.einsum(
        "bthn,chn->bthc", q_nope.astype(dtype),
        wk_b.reshape(d_latent, h, d_nope), **f32,
    )
    q_row = jnp.concatenate(
        [
            q_abs.astype(dtype), q_rope.astype(dtype),
            jnp.zeros((b, tq, h, width - d_latent - d_rope), dtype),
        ],
        axis=-1,
    ).reshape(b, tq * h, width)
    if _reads_pool_in_place(tq):
        # the kernel's result is a whole number of 128-lane tiles wide
        o_latent = latent_decode_attention(
            q_row, pool, block_table,
            q_pos[:, 0] + 1 if lengths is None else lengths,
            scale=scale, d_out=min(-(-d_latent // 128) * 128, width),
        )
    else:
        window = pool[block_table].reshape(b, n_keys, width)
        s = jnp.einsum("bre,bke->brk", q_row, window, **f32)
        p = _window_softmax(s.reshape(b, tq, h, n_keys), q_pos, scale, dtype)
        o_latent = jnp.einsum(
            "brk,bke->bre", p.reshape(b, tq * h, n_keys), window, **f32
        )
    o = jnp.einsum(
        "bthc,chv->bthv",
        o_latent[..., :d_latent].reshape(b, tq, h, d_latent).astype(dtype),
        wv_b.reshape(d_latent, h, -1), **f32,
    ).reshape(b, tq, -1)
    if lengths is not None:  # an idle row's result is zeros in either form
        o = jnp.where(lengths[:, None, None] > 0, o, 0.0)
    return o


def _absorbed_queries(q_nope, q_rope, wk_b, width, dtype):
    """``[q_nope wk_b[h]^T, q_rope, zeros]`` [B, Tq, H, width]: the queries
    of the absorbed form, laid out like the cached rows they meet."""
    b, tq, h, d_nope = q_nope.shape
    d_latent = wk_b.shape[0]
    q_abs = jnp.einsum(
        "bthn,chn->bthc", q_nope.astype(dtype),
        wk_b.reshape(d_latent, h, d_nope), preferred_element_type=jnp.float32,
    )
    pad = width - d_latent - q_rope.shape[-1]
    return jnp.concatenate(
        [q_abs.astype(dtype), q_rope.astype(dtype),
         jnp.zeros((b, tq, h, pad), dtype)],
        axis=-1,
    )


def _unfolded(o_latent, wv_b, lengths):
    """The absorbed form's result [B, Tq, H, >= d_latent] through
    ``wv_b``: [B, Tq, H * d_v] float32, zeros for a decode step's idle
    rows."""
    b, tq, h, _ = o_latent.shape
    d_latent = wv_b.shape[0]
    o = jnp.einsum(
        "bthc,chv->bthv", o_latent[..., :d_latent].astype(wv_b.dtype),
        wv_b.reshape(d_latent, h, -1), preferred_element_type=jnp.float32,
    ).reshape(b, tq, -1)
    if lengths is not None:
        o = jnp.where(lengths[:, None, None] > 0, o, 0.0)
    return o


# table entries whose indexer keys are gathered and scored at a time
INDEX_CHUNK_BLOCKS = 16
# rows of a decode step whose keys are chosen and listed at a time (a
# float32 sublane tile): :func:`select_live_rows`
SELECT_TILE_ROWS = 8


def _table_chunks(block_table, last, block_size):
    """How the loops below walk a block table ``INDEX_CHUNK_BLOCKS`` entries
    at a time: ``(table padded to whole chunks, entries a chunk, chunks,
    chunks that reach position max(last))``."""
    m = block_table.shape[1]
    step = min(INDEX_CHUNK_BLOCKS, m)
    n_chunks = -(-m // step)
    table = jnp.pad(block_table, ((0, 0), (0, n_chunks * step - m)))
    return table, step, n_chunks, jnp.max(last) // (block_size * step) + 1


def paged_index_scores(
    q_idx: jnp.ndarray,  # [B, Tq, J, d_idx], rotated
    w_idx: jnp.ndarray,  # [B, Tq, J] float32, the heads' weights
    idx_pool: jnp.ndarray,  # [N_blocks, block_size, d_idx]
    block_table: jnp.ndarray,  # [B, M] int32 pool block ids
    q_pos: jnp.ndarray,  # [B, Tq] int32 absolute query positions
    *,
    block_size: int,
    lengths: Optional[jnp.ndarray] = None,  # [B] int32; 0: the row idles
) -> jnp.ndarray:
    """The learned INDEXER's score of every cached token for every query
    (DeepSeek-V3.2-Exp lineage): ``I[t, s] = sum_j w[t, j] * relu(q[t, j]
    . k[s])`` over the ``J`` indexer heads, [B, Tq, M * block_size]
    float32, ``-inf`` where key ``s`` lies past the query (or past a
    decode row's ``lengths``).  The caller folds the constant factors
    into ``w_idx``.

    The indexer's keys live in a pool of their own, block for block
    beside the latent rows (one table, one allocator state).  They are
    read through the block table ``INDEX_CHUNK_BLOCKS`` entries at a time
    and only as far as the furthest query: the loop's trip count follows
    the positions, so a chunk early in a prompt does not pay for the
    table's width, and the [queries, heads, keys] products exist one
    chunk of keys at a time.  On the TPU a decode step (``Tq`` 1) reads
    the pool IN PLACE instead, each live row as far as its own length
    (:func:`~znicz_tpu.ops.pallas.sparse_index.index_decode_scores`).
    Products take the pool's dtype and accumulate in float32; ReLU and the
    sum over heads are float32."""
    with jax.named_scope("dsa_indexer"):
        b, tq, _, d_idx = q_idx.shape
        m = block_table.shape[1]
        key = jnp.arange(m * block_size)[None, None, :]
        seen = key <= q_pos[:, :, None]
        if lengths is not None:
            seen = seen & (key < lengths[:, None, None])
        if _reads_pool_in_place(tq):
            scores = index_decode_scores(
                q_idx[:, 0].astype(idx_pool.dtype), w_idx[:, 0], idx_pool,
                block_table, q_pos[:, 0] + 1 if lengths is None else lengths,
            )[:, None]
            return jnp.where(seen, scores, -jnp.inf)
        last = q_pos[:, -1] if lengths is None else jnp.maximum(lengths - 1, 0)
        table, step, n_chunks, needed = _table_chunks(block_table, last, block_size)
        q = q_idx.astype(idx_pool.dtype)

        def chunk(i, scores):
            blks = jax.lax.dynamic_slice_in_dim(table, i * step, step, axis=1)
            keys = idx_pool[blks].reshape(b, step * block_size, d_idx)
            s = jnp.einsum(
                "btjd,bkd->btjk", q, keys, preferred_element_type=jnp.float32
            )
            s = jnp.einsum("btjk,btj->btk", jax.nn.relu(s), w_idx)
            return jax.lax.dynamic_update_slice_in_dim(
                scores, s, i * step * block_size, axis=2
            )

        scores = jax.lax.fori_loop(
            0, needed, chunk,
            jnp.zeros((b, tq, n_chunks * step * block_size), jnp.float32),
        )[..., : m * block_size]
        return jnp.where(seen, scores, -jnp.inf)


def select_top_keys(scores: jnp.ndarray, top_k: int) -> jnp.ndarray:
    """Which keys are among the ``top_k`` best-scored of their query,
    EXACTLY: ``keep`` [B, Tq, keys] bool.  Keys whose score is ``-inf``
    (not visible) are never kept, so a query that sees no more than
    ``top_k`` keys keeps all of them; of equal scores at the cut the
    earlier keys are kept.

    No sort: the ``top_k``-th largest score of a row is found bit by bit
    (a float32's bits, turned so that they order as integers: 32 counts
    of ``score >= candidate`` over the row), which at 128 x 33,792 scores
    costs a twelfth of ``jax.lax.top_k``'s sort on the v5e (PERF.md
    section 6, PR 36), and the result is a mask over the keys as they lie
    in the pool, which is what the attention over them reads.

    EVERY row of ``scores`` is visited, whatever it holds: a prefill chunk
    (one row, ``Tq`` queries) calls this as it is; a decode step, most of
    whose rows idle, calls it a tile of its live rows at a time
    (:func:`select_live_rows`).  A row's mask depends on that row's scores
    alone and every step is an integer comparison or an integer sum, so
    the rows a call is given, and how many, cannot move a result."""
    with jax.named_scope("dsa_select"):
        bits = jax.lax.bitcast_convert_type(scores, jnp.int32)
        order = jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)
        k = jnp.int32(top_k)

        def count_at_least(t):
            return jnp.sum(order >= t, axis=-1, keepdims=True, dtype=jnp.int32)

        least = jnp.where(
            count_at_least(jnp.int32(0)) >= k, jnp.int32(0),
            jnp.int32(-(2 ** 31)),
        )
        for bit in range(30, -1, -1):
            candidate = least | jnp.int32(1 << bit)
            least = jnp.where(count_at_least(candidate) >= k, candidate, least)
        # ``least`` is the top_k-th largest (the smallest of all where
        # fewer than top_k are finite); ties at it go to the earlier keys
        above, ties = order > least, order == least
        room = k - jnp.sum(above, axis=-1, keepdims=True, dtype=jnp.int32)
        ties = ties & (jnp.cumsum(ties, axis=-1, dtype=jnp.int32) <= room)
        return (above | ties) & (scores > -jnp.inf)


# a masked key's score: finite, so that a chunk none of whose keys a query
# keeps does not turn the running softmax into inf - inf
_NEG = -1e30


def kept_latent_attention(
    q_nope: jnp.ndarray,  # [B, Tq, H, d_nope]
    q_rope: jnp.ndarray,  # [B, Tq, H, d_rope], rotated
    pool: jnp.ndarray,  # [N_blocks, block_size, W]: [c, rot(k_r), zeros]
    block_table: jnp.ndarray,  # [B, M] int32 pool block ids
    q_pos: jnp.ndarray,  # [B, Tq] int32 absolute query positions
    keep: jnp.ndarray,  # [B, Tq, M * block_size] bool: the keys attended
    wk_b: jnp.ndarray,  # [d_latent, H * d_nope]
    wv_b: jnp.ndarray,  # [d_latent, H * d_v]
    *,
    block_size: int,
    scale: float,
    lengths: Optional[jnp.ndarray] = None,  # [B] int32; 0: the row idles
) -> jnp.ndarray:
    """Absorbed latent attention of every query over the keys ``keep``
    names for it; [B, Tq, H * d_v] float32.  A query that keeps no key
    gives zeros.

    The rows are read through the block table as they lie in the pool,
    block by block, and a key a query does not keep is masked out of its
    softmax: what the selection spares is not the fetch (with 2,048 of a
    row's 3k-33k keys kept nearly every block of 128 holds one) but the
    need to form anything at the table's width.  On the TPU a decode step
    is :func:`~znicz_tpu.ops.pallas.latent_attention
    .latent_decode_attention` with ``keep`` as its mask: each live row's
    blocks by DMA as far as its own length.  A prefill chunk, and
    everything off the TPU, walks the table ``INDEX_CHUNK_BLOCKS`` entries
    at a time with a running softmax (the loop's trip count follows the
    furthest query, as :func:`paged_index_scores`'s does), so scores exist
    for one chunk of keys at a time and all ``Tq`` queries share each
    fetch."""
    with jax.named_scope("mla_sparse"):
        b, tq, h, _ = q_nope.shape
        d_latent = wk_b.shape[0]
        dtype, width = pool.dtype, pool.shape[-1]
        f32 = dict(preferred_element_type=jnp.float32)
        q_row = _absorbed_queries(q_nope, q_rope, wk_b, width, dtype)
        last = q_pos[:, -1] if lengths is None else jnp.maximum(lengths - 1, 0)
        if _reads_pool_in_place(tq):
            o_latent = latent_decode_attention(
                q_row[:, 0], pool, block_table,
                last + 1 if lengths is None else lengths, scale=scale,
                d_out=min(-(-d_latent // 128) * 128, width), keep=keep[:, 0],
            )[:, None]
            return _unfolded(o_latent, wv_b, lengths)
        o_latent = _kept_rows_walk(
            pool, block_table, keep, last, (b, tq, h), block_size=block_size,
            out_width=width,
            scores=lambda rows: jnp.einsum(
                "bthe,bke->bthk", q_row, rows, **f32
            ) * scale,
            # over the whole row: a slice of the fetched rows is a copy
            weighted=lambda p, rows: jnp.einsum(
                "bthk,bke->bthe", p, rows, **f32
            ),
        )
        return _unfolded(o_latent, wv_b, lengths)


def _kept_rows_walk(pool, block_table, keep, last, queries, *, block_size,
                    out_width, scores, weighted):
    """The shared PREFILL form of attention over the keys ``keep`` [B, Tq,
    M * block_size] names, whatever a cached row holds: the table is
    walked ``INDEX_CHUNK_BLOCKS`` entries at a time as far as the furthest
    query (``last`` [B]), each chunk's rows [B, keys, W] fetched once for
    all ``queries`` = (B, Tq, H), under a running softmax; a key a query
    does not keep is masked out of it, and a query that keeps none gives
    zeros.  ``scores(rows)`` -> [B, Tq, H, keys] float32, scaled;
    ``weighted(p, rows)`` -> [B, Tq, H, out_width] float32 with ``p`` in
    the pool's dtype: the two products of the row layout.  Returns [B, Tq,
    H, out_width] float32."""
    b, tq, h = queries
    dtype, width = pool.dtype, pool.shape[-1]
    table, step, n_chunks, needed = _table_chunks(block_table, last, block_size)
    keys = step * block_size
    keep = jnp.pad(
        keep, ((0, 0), (0, 0), (0, n_chunks * keys - keep.shape[-1]))
    )

    def chunk(i, carry):
        top, total, acc = carry
        blks = jax.lax.dynamic_slice_in_dim(table, i * step, step, axis=1)
        rows = pool[blks].reshape(b, keys, width)
        kept = jax.lax.dynamic_slice_in_dim(keep, i * keys, keys, axis=2)
        kept = kept[:, :, None, :]
        s = jnp.where(kept, scores(rows), _NEG)
        new_top = jnp.maximum(top, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(kept, jnp.exp(s - new_top), 0.0)
        turn = jnp.exp(top - new_top)
        acc = turn * acc + weighted(p.astype(dtype), rows)
        total = turn * total + jnp.sum(p, axis=-1, keepdims=True)
        return new_top, total, acc

    _, total, acc = jax.lax.fori_loop(
        0, needed, chunk,
        (
            jnp.full((b, tq, h, 1), _NEG, jnp.float32),
            jnp.zeros((b, tq, h, 1), jnp.float32),
            jnp.zeros((b, tq, h, out_width), jnp.float32),
        ),
    )
    return acc / jnp.maximum(total, 1e-30)


def kept_key_slots(keep: jnp.ndarray, top_k: int, *, block_size: int):
    """A mask over a row's keys turned into the list of the keys it names:
    ``keep`` [B, M * block_size] bool with at most ``top_k`` set a row ->
    ``(entry [B, top_k], offset [B, top_k], named [B, top_k] bool)``: slot
    ``j`` is the row's ``j``-th kept key, at ``offset`` within the block of
    table entry ``entry``; a slot past the row's last kept key is not
    ``named`` (entry and offset 0).

    No sort and no scatter, and nothing scalar is gathered: the kept keys
    are counted a block (one sum), a slot finds its block by comparing the
    running count with its own number (``top_k x M`` comparisons a row, a
    fused reduction), reads that block's mask by a product with 0 / 1 and
    finds its key inside it from the mask's running sum (a product with a
    triangle; counts to 128 are exact in bfloat16).

    Every row of ``keep`` is visited (``top_k x M`` comparisons and two
    products each, idle or not), and a row's list depends on that row's
    mask alone: a decode step hands over a tile of its live rows at a time
    (:func:`select_live_rows`) and reads what the whole batch would."""
    b, n_keys = keep.shape
    m = n_keys // block_size
    by_block = keep.reshape(b, m, block_size)
    count = jnp.sum(by_block, axis=-1, dtype=jnp.int32)  # [B, M]
    ends = jnp.cumsum(count, axis=1)
    slot = jnp.arange(top_k, dtype=jnp.int32)
    before = ends[:, None, :] <= slot[None, :, None]  # blocks wholly before
    entry = jnp.sum(before, axis=-1, dtype=jnp.int32)
    rank = slot[None, :] - jnp.sum(
        jnp.where(before, count[:, None, :], 0), axis=-1, dtype=jnp.int32
    )
    named = slot[None, :] < ends[:, -1:]
    entry = jnp.where(named, entry, 0)
    f32 = dict(preferred_element_type=jnp.float32)
    mask = jnp.einsum(
        "bjm,bmo->bjo",
        (entry[..., None] == jnp.arange(m)).astype(jnp.bfloat16),
        by_block.astype(jnp.bfloat16), **f32,
    )
    at = jnp.arange(block_size)
    running = jnp.einsum(
        "bjo,op->bjp", mask.astype(jnp.bfloat16),
        (at[:, None] <= at[None, :]).astype(jnp.bfloat16), **f32,
    )
    # the (rank + 1)-th kept key of the block lies after as many
    # positions as have a running sum of at most rank
    offset = jnp.sum(
        running <= rank[..., None].astype(jnp.float32), axis=-1,
        dtype=jnp.int32,
    )
    return entry, jnp.where(named, offset, 0), named


def kept_row_addresses(
    keep: jnp.ndarray, block_table: jnp.ndarray, top_k: int, *,
    block_size: int,
):
    """Where the keys ``keep`` [B, M * block_size] names lie in the pool:
    ``(address [B, top_k] int32, named [B, top_k] bool)``, slot ``j`` the
    row's ``j``-th kept key at pool row ``block * block_size + offset``
    (:func:`kept_key_slots` through ``block_table`` [B, M]); a slot that is
    not ``named`` holds address 0, the first row of ``NULL_BLOCK``.  The
    block of a slot's table entry is a sum under 0 / 1 (a fused reduction;
    nothing scalar is gathered)."""
    entry, offset, named = kept_key_slots(keep, top_k, block_size=block_size)
    blk = jnp.sum(
        jnp.where(
            entry[..., None] == jnp.arange(block_table.shape[1]),
            block_table[:, None, :], 0,
        ),
        axis=-1, dtype=jnp.int32,
    )
    return jnp.where(named, blk * block_size + offset, 0), named


def select_live_rows(
    scores: jnp.ndarray,  # [B, 1, M * block_size] float32, -inf: not seen
    lengths: jnp.ndarray,  # [B] int32; 0: the row idles
    top_k: int,
    *,
    block_table: Optional[jnp.ndarray] = None,  # [B, M]: list the kept rows
    block_size: Optional[int] = None,
):
    """A DECODE step's selection, over the rows that decode and no other:
    :func:`select_top_keys` (and, with ``block_table``, :func:`kept_row
    _addresses`) a TILE of ``SELECT_TILE_ROWS`` live rows at a time, in a
    loop inside the one compiled program whose trip count follows the live
    rows (``lengths > 0``, live rows first in slot order): at 22 of 64
    slots live three tiles run, not the batch's eight.

    Returns ``(kept, scored, selected, visited)``: ``kept`` is the mask
    ``keep`` [B, 1, keys] (rows never visited all False) or, with
    ``block_table``, the listing ``(address [B, top_k], named [B, top_k])``
    (rows never visited name nothing; the ``[B, keys]`` mask then exists a
    tile at a time and never in HBM); ``scored`` / ``selected``, the keys
    seen and kept, int32 sums over the rows; ``visited``, the rows the loop
    went over, ``tiles x SELECT_TILE_ROWS``.

    IDENTICAL to the two functions over the whole batch: a row's result
    depends on that row's scores alone, by integer compares and sums, so
    a live row reads what it would in any company; an idle row's scores
    are all ``-inf`` (:func:`paged_index_scores` masks by ``lengths``), so
    the whole batch gives it nothing kept and nothing named, which is what
    an unvisited row starts as, and what the idle rows that fill the last
    tile come out as.  The counts and ties run under scope ``dsa_select``,
    the listing under ``gqa_sparse``, as they do over the whole batch."""
    b, tq, _ = scores.shape
    if tq != 1:
        raise ValueError(f"lengths are a decode step's; got {tq} queries a row")
    return _select_tiles(
        scores, lengths, block_table, top_k=top_k, block_size=block_size,
        r=min(SELECT_TILE_ROWS, b),
    )


# jitted for the reason kept_rows_attention._attend is: a tower's layers
# share one trace and one lowering of the loop (and a step run operation by
# operation compiles it once, not anew for every call's closures)
@partial(jax.jit, static_argnames=("top_k", "block_size", "r"))
def _select_tiles(scores, lengths, block_table, *, top_k, block_size, r):
    """:func:`select_live_rows` with tiles of ``r`` rows."""
    b, _, n_keys = scores.shape
    live = lengths > 0
    n_tiles = -(-jnp.sum(live, dtype=jnp.int32) // r)
    # the batch's rows, the live ones first; past the batch: no row
    order = jnp.pad(
        jnp.argsort(~live, stable=True).astype(jnp.int32), (0, -b % r),
        constant_values=b,
    )

    if block_table is None:
        start = jnp.zeros((b, n_keys), bool)

        def record(kept, rows, keep):
            with jax.named_scope("dsa_select"):
                return kept.at[rows].set(keep, mode="drop"), keep
    else:
        start = (jnp.zeros((b, top_k), jnp.int32), jnp.zeros((b, top_k), bool))

        def record(kept, rows, keep):
            with jax.named_scope("gqa_sparse"):
                address, named = kept_row_addresses(
                    keep, block_table[rows], top_k, block_size=block_size
                )
                return (
                    kept[0].at[rows].set(address, mode="drop"),
                    kept[1].at[rows].set(named, mode="drop"),
                ), named

    def tile(i, carry):
        kept, scored, selected = carry
        with jax.named_scope("dsa_select"):
            rows = jax.lax.dynamic_slice_in_dim(order, i * r, r)
            # a row past the batch reads the last one's: nothing seen
            s = jnp.where((rows < b)[:, None], scores[rows, 0], -jnp.inf)
        kept, chosen = record(kept, rows, select_top_keys(s[:, None], top_k)[:, 0])
        return (
            kept, scored + jnp.sum(s > -jnp.inf, dtype=jnp.int32),
            selected + jnp.sum(chosen, dtype=jnp.int32),
        )

    kept, scored, selected = jax.lax.fori_loop(
        0, n_tiles, tile, (start, jnp.int32(0), jnp.int32(0))
    )
    if block_table is None:
        kept = kept[:, None]
    return kept, scored, selected, n_tiles * r


def kept_rows_fetched(lengths: jnp.ndarray, top_k: int) -> jnp.ndarray:
    """Cached rows ONE layer's :func:`kept_gqa_attention` fetches in a
    decode step (int32 scalar), :func:`paged_gqa_rows_read`'s twin: a live
    row the keys it kept, ``min(length, top_k)``; every other slot of the
    fetch names the first row of ``NULL_BLOCK``, which is not counted."""
    return jnp.sum(jnp.minimum(lengths, top_k), dtype=jnp.int32)


def kept_gqa_attention(
    q: jnp.ndarray,  # [B, Tq, H, D]
    pool: jnp.ndarray,  # [N_blocks, block_size, 2 * G * D]: [v, k] a token
    block_table: jnp.ndarray,  # [B, M] int32 pool block ids
    q_pos: jnp.ndarray,  # [B, Tq] int32 absolute query positions
    keep: Optional[jnp.ndarray],  # [B, Tq, M * block_size] bool: the keys attended
    *,
    block_size: int,
    n_kv_heads: int,
    top_k: int,  # the most keys ``keep`` names a query
    scale: float,
    lengths: Optional[jnp.ndarray] = None,  # [B] int32; 0: the row idles
    listed=None,  # a decode step's (address, named) [B, top_k], for ``keep``
) -> jnp.ndarray:
    """Grouped-query attention of every query over the keys ``keep`` names
    for it, over ``[v, k]`` rows (:func:`gqa_cache_row`); [B, Tq, H * D]
    float32.  A query that keeps no key gives zeros.

    A DECODE step (``lengths`` given, ``Tq`` 1) FETCHES THE KEPT ROWS AND
    NOTHING ELSE: the mask becomes each row's list of pool rows
    (:func:`kept_row_addresses`) and the queries meet the rows they name
    as stored (:func:`paged_gqa_attention`'s layout: one product for all
    heads, the zeros add exactly), whatever the row's length (a walk of
    every block under the mask, :func:`kept_latent_attention`'s decode
    form, reads the whole row, 32 x the kept keys at 66k).  Given ``keep``
    the list is made here for EVERY row of the batch, idle or not; the
    serving path hands over ``listed`` instead, which :func:`select_live
    _rows` made for the live rows alone (the same list: an idle row's mask
    names nothing either way).  On the TPU, over a pool it can read
    (:func:`_reads_kept_rows_in_place`), the attention is :func:`~znicz_tpu
    .ops.pallas.kept_rows_attention.kept_rows_decode_attention`: the LIVE
    rows' kept keys by DMA from the pool where it lies, attended in VMEM.
    Elsewhere the rows are gathered ([B, top_k, W], one fetch a slot of
    the list, named or not, idle rows' too, written out and read back by
    the two products), which is also the kernel's twin.  Which of the two
    a decode program was built with is counted once a layer,
    ``znicz_serve_kept_rows_attention_total{form}``.  A PREFILL chunk
    walks the table under the mask (:func:`_kept_rows_walk`, shared with
    the latent rows) with the products GROUPED a K/V head: at ``Tq * H`` =
    4,096 query rows the zeros of the as-stored layout would cost ``H /
    G`` times the FLOPs."""
    with jax.named_scope("gqa_sparse"):
        b, tq, h, d = q.shape
        g, half = n_kv_heads, n_kv_heads * d
        dtype = pool.dtype
        f32 = dict(preferred_element_type=jnp.float32)
        if pool.shape[-1] != 2 * half:
            raise ValueError(
                f"a cached row is [v, k] of {n_kv_heads} heads x {d}: want "
                f"{2 * half} lanes, the pool has {pool.shape[-1]}"
            )
        if lengths is not None:
            if tq != 1:
                raise ValueError(
                    f"lengths are a decode step's; got {tq} queries a row"
                )
            address, named = listed or kept_row_addresses(
                keep[:, 0], block_table, top_k, block_size=block_size
            )
            q_row = _gqa_query_rows(q, g, dtype)
            in_place = _reads_kept_rows_in_place(tq, pool)
            _KEPT_ROWS_FORM.labels(
                form="in_place" if in_place else "gathered"
            ).inc()
            if in_place:
                # the slots are named in order: the first so many of a row
                o = kept_rows_attention.kept_rows_decode_attention(
                    q_row, pool, address,
                    jnp.sum(named, axis=-1, dtype=jnp.int32), scale=scale,
                    d_out=min(-(-half // 128) * 128, 2 * half),
                )
                o = _gqa_own_values(o[:, None], g, d)
                return jnp.where(lengths[:, None, None] > 0, o, 0.0)
            rows = pool.reshape(-1, 2 * half)[address]  # [B, top_k, W]
            s = jnp.einsum("bre,bke->brk", q_row, rows, **f32) * scale
            named = named[:, None, :]
            s = jnp.where(named, s, _NEG)
            p = jnp.where(
                named, jnp.exp(s - jnp.max(s, axis=-1, keepdims=True)), 0.0
            )
            total = jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
            # over the whole row: a slice of the fetched rows is a copy
            o = jnp.einsum("brk,bke->bre", (p / total).astype(dtype), rows, **f32)
            o = _gqa_own_values(o[:, None], g, d)
            return jnp.where(lengths[:, None, None] > 0, o, 0.0)
        scores, weighted = _grouped_products(q, g, dtype, scale)
        o = _kept_rows_walk(
            pool, block_table, keep, q_pos[:, -1], (b, tq, h),
            block_size=block_size, out_width=d, scores=scores,
            weighted=weighted,
        )
        return o.reshape(b, tq, h * d)


def _grouped_products(q, g, dtype, scale):
    """The two products of :func:`_kept_rows_walk` for queries ``q`` [B,
    Tq, H, D] over ``[v, k]`` rows of ``g`` K/V heads, GROUPED a K/V head
    (``H / g`` query heads meet their own head's keys and values and no
    zeros): ``(scores, weighted)``."""
    b, tq, h, d = q.shape
    f32 = dict(preferred_element_type=jnp.float32)
    qg = q.astype(dtype).reshape(b, tq, g, h // g, d)

    def split(rows):  # [B, keys, W] -> v, k [B, keys, G, D]
        rows = rows.reshape(b, -1, 2, g, d)
        return rows[:, :, 0], rows[:, :, 1]

    def scores(rows):
        return jnp.einsum(
            "btgrd,bkgd->btgrk", qg, split(rows)[1], **f32
        ).reshape(b, tq, h, -1) * scale

    def weighted(p, rows):
        return jnp.einsum(
            "btgrk,bkgd->btgrd", p.reshape(b, tq, g, h // g, -1),
            split(rows)[0], **f32,
        ).reshape(b, tq, h, d)

    return scores, weighted


def paged_selected_gqa_attention(
    q, q_idx, w_idx, pool, idx_pool, block_table, q_pos, *, block_size: int,
    n_kv_heads: int, top_k: int, scale: Optional[float] = None,
    lengths: Optional[jnp.ndarray] = None,
):
    """Grouped-query attention that keeps ``top_k`` keys a query, chosen
    by a learned indexer, over a paged pool of ``[v, k]`` rows and, block
    for block beside it, a pool of the indexer's keys: :func:`paged_index
    _scores`, the selection (both :func:`paged_selected_latent
    _attention`'s), :func:`kept_gqa_attention`.  A prefill chunk selects
    with :func:`select_top_keys` over its one row; a decode step
    (``lengths``) chooses AND lists the kept keys of its live rows alone,
    a tile at a time (:func:`select_live_rows`: the result is the whole
    batch's).  Returns ``(o [B, Tq, H * D] float32, scored, selected,
    visited)`` with that function's contract."""
    scores = paged_index_scores(
        q_idx, w_idx, idx_pool, block_table, q_pos, block_size=block_size,
        lengths=lengths,
    )
    keep = listed = None
    if lengths is None:
        keep, scored, selected, visited = _select_every_row(scores, top_k)
    else:
        listed, scored, selected, visited = select_live_rows(
            scores, lengths, top_k, block_table=block_table,
            block_size=block_size,
        )
    o = kept_gqa_attention(
        q, pool, block_table, q_pos, keep, block_size=block_size,
        n_kv_heads=n_kv_heads, top_k=top_k, lengths=lengths, listed=listed,
        scale=1.0 / np.sqrt(q.shape[-1]) if scale is None else scale,
    )
    return o, scored, selected, visited


def _select_every_row(scores, top_k):
    """:func:`select_top_keys` over all of ``scores`` [B, Tq, keys] with
    :func:`select_live_rows`'s sums: ``(keep, scored, selected, visited =
    B)``."""
    keep = select_top_keys(scores, top_k)
    return (
        keep, jnp.sum(scores > -jnp.inf, dtype=jnp.int32),
        jnp.sum(keep, dtype=jnp.int32), jnp.int32(scores.shape[0]),
    )


def paged_selected_latent_attention(
    q_nope, q_rope, q_idx, w_idx, pool, idx_pool, block_table, q_pos, wk_b,
    wv_b, *, block_size: int, scale: float, top_k: int,
    lengths: Optional[jnp.ndarray] = None,
):
    """Latent attention that keeps ``top_k`` keys a query, chosen by a
    learned indexer, over a paged pool of latent rows ``[c, rot(k_r),
    zeros]`` and, block for block beside it, a pool of the indexer's keys:
    :func:`paged_index_scores` of every cached token, the selection,
    :func:`kept_latent_attention`.  The same three steps serve a prefill
    chunk (``Tq`` queries of one row: :func:`select_top_keys` over it) and
    a decode step (``Tq`` 1, ``lengths``: 0 marks a row that idles, whose
    result is zeros; :func:`select_live_rows` visits the live rows a tile
    at a time and leaves an idle row's mask False, which is what the
    selection over the whole batch gives it).  Returns ``(o [B, Tq, H *
    d_v] float32, scored, selected, visited)``: the keys the indexer
    scored and the keys attention kept, int32 sums over the call's
    queries, and the rows the selection went over."""
    scores = paged_index_scores(
        q_idx, w_idx, idx_pool, block_table, q_pos, block_size=block_size,
        lengths=lengths,
    )
    if lengths is None:
        keep, scored, selected, visited = _select_every_row(scores, top_k)
    else:
        keep, scored, selected, visited = select_live_rows(
            scores, lengths, top_k
        )
    o = kept_latent_attention(
        q_nope, q_rope, pool, block_table, q_pos, keep, wk_b, wv_b,
        block_size=block_size, scale=scale, lengths=lengths,
    )
    return o, scored, selected, visited


def paged_window_latent_attention(
    q_nope: jnp.ndarray,  # [B, Tq, H, d_nope]
    q_rope: jnp.ndarray,  # [B, Tq, H, d_rope], rotated
    pool: jnp.ndarray,  # [N_blocks, block_size, >= d_latent + d_rope]
    block_table: jnp.ndarray,  # [B, M] int32: a RING
    q_pos: jnp.ndarray,  # [B, Tq] int32 absolute query positions
    wk_b: jnp.ndarray,  # [d_latent, H * d_nope]
    wv_b: jnp.ndarray,  # [d_latent, H * d_v]
    *,
    block_size: int,
    scale: float,
    window: int,
    lengths: Optional[jnp.ndarray] = None,  # [B] int32; 0: the row idles
) -> jnp.ndarray:
    """Absorbed latent attention over the last ``window`` keys, the
    query's own among them, of a paged pool of latent rows whose table is
    a RING (:func:`ring_key_positions`; a call's queries of one row lie in
    one block); [B, Tq, H * d_v] float32.  On the TPU a decode step reads
    the pool in place through :func:`~znicz_tpu.ops.pallas
    .latent_attention.latent_decode_attention` (the ring turned so that
    the window's first block leads, as :func:`paged_gqa_attention` does);
    a prefill chunk, and everything off the TPU, gathers the ring, which
    is a few blocks wide whatever the row's length."""
    with jax.named_scope("mla_window"):
        b, tq, h, _ = q_nope.shape
        d_latent = wk_b.shape[0]
        dtype, width = pool.dtype, pool.shape[-1]
        if lengths is not None and tq != 1:
            raise ValueError(
                f"lengths are a decode step's; got {tq} queries a row"
            )
        q_row = _absorbed_queries(q_nope, q_rope, wk_b, width, dtype)
        if _reads_pool_in_place(tq):
            table, keys, starts = _window_in_table_order(
                block_table, q_pos[:, 0] + 1 if lengths is None else lengths,
                block_size=block_size, window=window,
            )
            pad = -h % 16  # the kernel wants whole (16, 128) tiles of rows
            o_latent = latent_decode_attention(
                jnp.pad(q_row[:, 0], ((0, 0), (0, pad), (0, 0))), pool, table,
                keys, scale=scale, d_out=min(-(-d_latent // 128) * 128, width),
                starts=starts,
            )[:, None, :h]
            return _unfolded(o_latent, wv_b, lengths)
        n_keys = block_table.shape[1] * block_size
        rows = pool[block_table].reshape(b, n_keys, width)
        k_pos = ring_key_positions(
            block_table.shape[1], block_size, q_pos[:, -1]
        )[:, None, None, :]
        at = q_pos[:, :, None, None]
        valid = (k_pos <= at) & (k_pos >= 0) & (k_pos > at - window)
        f32 = dict(preferred_element_type=jnp.float32)
        s = jnp.einsum("bthe,bke->bthk", q_row, rows, **f32)
        s = jnp.where(valid, s * scale, -jnp.inf)
        p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
        p = (p / jnp.sum(p, axis=-1, keepdims=True)).astype(dtype)
        return _unfolded(
            jnp.einsum("bthk,bke->bthe", p, rows, **f32), wv_b, lengths
        )


def init_mha_params(
    d_model: int,
    n_heads: int,
    *,
    head_dim: Optional[int] = None,
    weights_stddev: Optional[float] = None,
    weights_filling: str = "gaussian",
    rand_name: str = "default",
    dtype=jnp.float32,
) -> Dict[str, jnp.ndarray]:
    gen = prng.get(rand_name)
    head_dim = head_dim or d_model // n_heads
    if weights_stddev is None:
        weights_stddev = 1.0 / np.sqrt(d_model)
    inner = n_heads * head_dim
    params = {}
    for name in ("wq", "wk", "wv"):
        params[name] = jnp.asarray(
            fill(gen, (d_model, inner), weights_filling, weights_stddev), dtype
        )
    params["wo"] = jnp.asarray(
        fill(gen, (inner, d_model), weights_filling, weights_stddev), dtype
    )
    return params


def mha(
    params: Dict[str, jnp.ndarray],
    x: jnp.ndarray,  # [B, T, d_model]
    *,
    n_heads: int,
    causal: bool = False,
    attention_fn=dot_product_attention,
) -> jnp.ndarray:
    """Multi-head self-attention block (projections + attention + output).

    ``attention_fn`` is pluggable so the ring-parallel variant drops in.
    """
    b, t, _ = x.shape
    def proj(w):
        y = jnp.dot(x, w, preferred_element_type=jnp.float32).astype(x.dtype)
        return y.reshape(b, t, n_heads, -1)

    q, k, v = proj(params["wq"]), proj(params["wk"]), proj(params["wv"])
    o = attention_fn(q, k, v, causal=causal)
    o = o.reshape(b, t, -1)
    return jnp.dot(o, params["wo"], preferred_element_type=jnp.float32).astype(
        x.dtype
    )
