"""What every tower the paged engine serves through its ``model=`` seam
shares: the three functions the engine needs of a tower —
:meth:`PagedTower.init_pools`, :meth:`PagedTower.prefill_chunk`,
:meth:`PagedTower.decode_step` — with the classic tower's contracts
(:mod:`znicz_tpu.workflow.generate`: one ``[1, block_size]`` chunk a call,
per-row positions, writes of idle rows to ``NULL_BLOCK``, validity by
absolute key index), the walk over the layers, how a table's entry
addresses a pool block, and the helpers the towers' blocks are written in.

A tower (:mod:`~znicz_tpu.workflow.latent_lm`, :mod:`~znicz_tpu.workflow
.window_lm`, :mod:`~znicz_tpu.workflow.sparse_latent_lm`,
:mod:`~znicz_tpu.workflow.sparse_gqa_lm`, :mod:`~znicz_tpu.workflow
.gated_window_lm`) is a frozen dataclass on :class:`PagedTower`: a
hashable description, so it is a static argument of the engine's compiled
programs.  It supplies its fields and ``from_config``, ``routed_layers``,
``max_positions``, ``rms_eps``, and:

``cache_kinds`` / ``layer_kinds``
    the :class:`~znicz_tpu.workflow.generate.CacheKind` s it keeps and the
    kind of each layer's pool.  The engine then hands the functions here
    ``{kind: table}`` and ``n_blocks`` by kind; a window kind's table is a
    ring (entry ``(position // block_size) % width``).  A tower that
    declares none keeps ONE bare table and a bare ``n_blocks``.
``_pool_rows(block, kind)``
    what one layer's pool holds: ``({"kv": lanes, ...}, dtype)``.
``_block_step(block, kind, x, pool, write, table, q_pos, row_mask, *,
block_size, lengths, decode)``
    ONE layer over ``x`` [B, Tq, D] float32: ``write(pool_array, rows)``
    scatters the call's new rows into a pool array, attention reads through
    ``table``.  Returns ``(x, pool, pairs, selection)``: ``pairs`` [held]
    counts the (token, choice) pairs each held expert computed (None in a
    dense layer), ``selection`` the ``(scored, selected, visited)`` sums of
    a layer whose indexer selects keys (None where none does).
``_decode_reads(tables, lengths, *, block_size)``
    what a decode step adds to ``load``: the cached rows its layers read.

Parameter tree of every tower: ``[{"embed"}, block_0, ..., block_{L-1},
{"final_norm", "head"}]``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from znicz_tpu.ops.normalization import rms_norm
from znicz_tpu.workflow.generate import NULL_BLOCK

GLOBAL, WINDOW = "global", "window"


def _dot(a, w):
    """``a @ w`` with ``a`` rounded to the weights' dtype and the sum
    kept in float32."""
    return jnp.dot(a.astype(w.dtype), w, preferred_element_type=jnp.float32)


def _gated(h, gate, up, down):
    return _dot(jax.nn.silu(_dot(h, gate)) * _dot(h, up), down)


def _head_logits(params, x, eps):
    """The final norm and the head (whole, or this chip's slice of the
    vocabulary) over ``x`` [B, D]."""
    return _dot(rms_norm(x, params[-1]["final_norm"], eps=eps), params[-1]["head"])


def _chunk_row(x, last):
    """The row of a prefill chunk ``x`` [1, C, D] whose logits the call
    returns: in-chunk index ``last``, the chunk's final one by default."""
    if last is None:
        return x[:, -1]
    return jax.lax.dynamic_index_in_dim(x, last, axis=1, keepdims=False)


def _tiles(lanes: int) -> int:
    """``lanes`` rounded up to whole 128-lane tiles."""
    return -(-lanes // 128) * 128


def _expert_load(per_layer) -> dict:
    """What one call's routed layers did, as small int32 sums that come
    back with the call's outputs: ``pairs`` [held] (token, choice) pairs
    by held expert, ``busiest`` the busiest expert's pairs summed over the
    layers, ``idle`` experts that received no pair, summed likewise
    (empty where no layer routes)."""
    if not per_layer:
        return {}
    stacked = jnp.stack(per_layer)  # [layers, held]
    return {
        "pairs": jnp.sum(stacked, axis=0),
        "busiest": jnp.sum(jnp.max(stacked, axis=1)),
        "idle": jnp.sum(stacked == 0, dtype=jnp.int32),
    }


def _selection_load(per_layer) -> dict:
    """The selecting layers' ``(scored, selected, visited)`` sums as the
    load's ``sparse_scored`` / ``sparse_selected`` / ``sparse_rows``: the
    keys ONE layer's indexer scored and its attention read over the call's
    queries, and the rows its selection went over (a prefill chunk's one; a
    decode step's live rows in whole tiles, :func:`~znicz_tpu.ops.attention
    .select_live_rows`), each the mean over the layers that select (empty
    where none does)."""
    if not per_layer:
        return {}
    names = ("sparse_scored", "sparse_selected", "sparse_rows")
    return {
        name: sum(sums) // len(per_layer)
        for name, sums in zip(names, zip(*per_layer))
    }


def _rows_a_layer(by_kind, layer_kinds):
    """The mean over a tower's layers of a count ONE layer of each kind
    reports."""
    return sum(by_kind[k] for k in layer_kinds) // len(layer_kinds)


class PagedTower:
    """The base of a tower's frozen dataclass (no fields of its own)."""

    cache_kinds = None  # ONE kind of cached state: a bare table, a bare n_blocks

    def _by_kind(self, value):
        """A call's table(s), or ``n_blocks``, by cache kind: the bare
        value of a tower that declares no kinds sits under None."""
        return value if self.cache_kinds else {None: value}

    def _layer_kinds(self, params):
        if self.cache_kinds:
            return self.layer_kinds
        return (None,) * (len(params) - 2)

    @property
    def _ring(self) -> bool:
        """Whether a table's entry is taken modulo the table's width: in
        every kind of a tower one of whose kinds has a window (a plain
        kind's table is as wide as the row is long, so it changes nothing
        there), in none of a tower that has no window."""
        return any(k.window is not None for k in self.cache_kinds or ())

    # -- the cache ----------------------------------------------------------

    def init_pools(self, params, n_blocks, block_size: int):
        """A layer: one zeroed ``[n_blocks[kind], block_size, lanes]`` pool
        for each array :meth:`_pool_rows` names (``"kv"``, and ``"idx"``
        beside it where the layer has an indexer: the same blocks, so one
        table and one allocator state serve both); block ``NULL_BLOCK``
        reserved in each kind, as in ``init_paged_kv``."""
        n_blocks = self._by_kind(n_blocks)
        if min(n_blocks.values()) < 2 or block_size < 1:
            raise ValueError(
                f"want n_blocks >= 2 a kind (one is the reserved null block) "
                f"and block_size >= 1; got {n_blocks}, {block_size}"
            )
        pools = []
        for block, kind in zip(params[1:-1], self._layer_kinds(params)):
            lanes, dtype = self._pool_rows(block, kind)
            pools.append({
                name: jnp.zeros((n_blocks[kind], block_size, width), dtype)
                for name, width in lanes.items()
            })
        return pools

    # -- the tower ----------------------------------------------------------

    def _tower(self, params, x, pools, writes, tables, q_pos, row_mask, *,
               block_size, lengths, decode):
        """``(x, pools, load)``: every layer's :meth:`_block_step` in turn,
        ``load`` the expert-load and selection sums."""
        new_pools, pairs_by_layer, selections = [], [], []
        layers = zip(params[1:-1], pools, self._layer_kinds(params))
        for block, pool, kind in layers:
            x, pool, pairs, selection = self._block_step(
                block, kind, x, pool, writes[kind], tables[kind], q_pos,
                row_mask, block_size=block_size, lengths=lengths,
                decode=decode,
            )
            new_pools.append(pool)
            if pairs is not None:
                pairs_by_layer.append(pairs)
            if selection is not None:
                selections.append(selection)
        load = dict(_expert_load(pairs_by_layer), **_selection_load(selections))
        return x, new_pools, load

    # Where in a call the blocks it writes are resolved depends on the tower
    # (``if self._ring`` below): with a ring after the embedding lookup,
    # without one first.  Nothing computed needs either place.  Both stay
    # because the order of the traced operations is part of a compiled
    # program's text, which names its compile-cache entry and its fusions
    # (``tools/lowered_text.py`` holds the five towers' ten programs to it);
    # a change that alters these programs anyway (ROADMAP S8.1, S9) keeps
    # one place.

    def prefill_chunk(
        self, params, pools, table, tokens, offset, *, block_size, last=None,
    ):
        """ONE aligned ``[1, block_size]`` chunk of a prompt through the
        tower, ``table`` the row's ``[width]`` (``{kind: [width]}`` of a
        tower that declares kinds); ``(pools, logits [1, vocab], load)`` at
        in-chunk index ``last`` (the chunk's final position by default).
        Positions past ``last`` are right-padding: they write rows no
        query reaches (see ``paged_prefill_chunk``) and are routed to no
        expert."""
        c = tokens.shape[1]
        if c != block_size:
            raise ValueError(
                f"chunk length {c} must equal block_size {block_size} "
                "(one chunk == one block)"
            )
        table, ring = self._by_kind(table), self._ring

        def block_of(t):
            entry = offset // block_size
            return t[entry % t.shape[0] if ring else entry]

        if not ring:
            blks = {kind: block_of(t) for kind, t in table.items()}
        x = params[0]["embed"][tokens].astype(jnp.float32)
        q_pos = offset + jnp.arange(c)[None, :]
        real = None if last is None else (jnp.arange(c) <= last)[None, :]
        if ring:
            blks = {kind: block_of(t) for kind, t in table.items()}

        def write_into(blk):
            return lambda pool, new: pool.at[blk].set(new[0])

        x, pools, load = self._tower(
            params, x, pools, {k: write_into(blk) for k, blk in blks.items()},
            {k: t[None] for k, t in table.items()}, q_pos, real,
            block_size=block_size, lengths=None, decode=False,
        )
        logits = _head_logits(params, _chunk_row(x, last), self.rms_eps)
        return pools, logits, load

    def decode_step(
        self, params, pools, tables, token, pos, *, block_size,
        write_mask=None,
    ):
        """One incremental step: ``token`` [B] at per-row positions ``pos``
        [B], ``tables`` ``[B, width]`` (``{kind: [B, width]}`` of a tower
        that declares kinds) -> ``(pools, logits [B, vocab], load)``.  Rows
        with ``write_mask`` False (done, idle) write to ``NULL_BLOCK``,
        attend nothing and are routed to no expert.  ``load`` also holds
        what the tower's :meth:`_decode_reads` counts of this step."""
        by_kind, ring = self._by_kind(tables), self._ring
        rows = jnp.arange(token.shape[0])

        def block_of(t):
            entry = pos // block_size
            return t[rows, entry % t.shape[1] if ring else entry]

        def live(value, idle):
            if write_mask is None:
                return value
            return jnp.where(write_mask, value, idle)

        if not ring:
            blks = {kind: block_of(t) for kind, t in by_kind.items()}
        lengths = pos + 1
        if not ring:
            blks = {kind: live(blk, NULL_BLOCK) for kind, blk in blks.items()}
        lengths = live(lengths, 0)
        slot = pos % block_size
        x = params[0]["embed"][token[:, None]].astype(jnp.float32)
        if ring:
            blks = {
                kind: live(block_of(t), NULL_BLOCK) for kind, t in by_kind.items()
            }

        def write_into(blk):
            return lambda pool, new: pool.at[blk, slot].set(new[:, 0])

        x, pools, load = self._tower(
            params, x, pools, {k: write_into(blk) for k, blk in blks.items()},
            by_kind, pos[:, None],
            None if write_mask is None else write_mask[:, None],
            block_size=block_size, lengths=lengths, decode=True,
        )
        load = dict(
            load, **self._decode_reads(tables, lengths, block_size=block_size)
        )
        return pools, _head_logits(params, x[:, 0], self.rms_eps), load
