"""The contract every tower behind the engine's ``model=`` seam shares
(workflow/paged_tower.py), held once for all five on the toy towers their
own test modules build: ONE ``prefill_chunk`` / ``decode_step`` / layer
loop / ``init_pools``, a chunk of one block, an idle row's writes in
``NULL_BLOCK``, and how a table's entry addresses a pool block by kind.

What each tower's ``_block_step`` computes is held against its reference
in ``test_latent_lm.py``, ``test_window_gqa_lm.py``, ``test_sparse_latent
_lm.py``, ``test_sparse_gqa_lm.py`` and ``test_gated_window_lm.py``."""

import jax.numpy as jnp
import numpy as np
import pytest

from znicz_tpu.workflow.generate import NULL_BLOCK
from znicz_tpu.workflow.paged_tower import PagedTower

import test_gated_window_lm  # isort: skip
import test_latent_lm  # isort: skip
import test_sparse_gqa_lm  # isort: skip
import test_sparse_latent_lm  # isort: skip
import test_window_gqa_lm  # isort: skip

BS = 4  # block size
PLAIN_WIDTH = 16  # entries of a plain kind's table: 64 positions
SENTINEL = 7.0  # what every pool row holds before a call
TOWERS = {
    "latent": test_latent_lm.Toy,
    "window-gqa": test_window_gqa_lm.Toy,
    "sparse-latent": test_sparse_latent_lm.Toy,
    "sparse-gqa": test_sparse_gqa_lm.Toy,
    "gated-window-gqa": test_gated_window_lm.Toy,
}
_built = {}


@pytest.fixture(params=list(TOWERS))
def toy(request):
    if request.param not in _built:
        _built[request.param] = TOWERS[request.param]()
    return _built[request.param]


class _Cache:
    """Pools filled with ``SENTINEL`` and, for ``rows`` rows, tables whose
    every entry names a block of its own: by kind where the tower declares
    kinds (a window kind's a ring just wider than its window), bare where it
    declares none."""

    def __init__(self, model, params, rows):
        windows = {k.name: k.window for k in model.cache_kinds or ()}
        self._bare = not windows
        windows = windows or {None: None}
        self.ring = {kind: w is not None for kind, w in windows.items()}
        self.widths = {
            kind: PLAIN_WIDTH if w is None else w // BS + 2
            for kind, w in windows.items()
        }
        self.tables = {
            kind: 1 + np.arange(rows * width, dtype=np.int32).reshape(rows, width)
            for kind, width in self.widths.items()
        }
        n_blocks = {kind: 1 + rows * width for kind, width in self.widths.items()}
        self.layer_kinds = (
            [None] * (len(params) - 2) if self._bare else model.layer_kinds
        )
        self.pools = [
            {name: jnp.full_like(a, SENTINEL) for name, a in pool.items()}
            for pool in model.init_pools(
                params, n_blocks[None] if self._bare else n_blocks, BS
            )
        ]

    def call_tables(self, row=None):
        """The tables as a call takes them: one row's for a prefill chunk,
        every row's for a decode step."""
        tables = {
            kind: jnp.asarray(t if row is None else t[row])
            for kind, t in self.tables.items()
        }
        return tables[None] if self._bare else tables

    def touched(self, new_pools):
        """By layer and pool array: ``{block: sorted slots}`` that no
        longer hold the sentinel."""
        out = []
        for kind, pool in zip(self.layer_kinds, new_pools):
            for name, array in pool.items():
                changed = np.any(np.asarray(array, np.float32) != SENTINEL, axis=-1)
                out.append((kind, name, {
                    int(blk): sorted(np.flatnonzero(changed[blk]).tolist())
                    for blk in np.flatnonzero(changed.any(axis=1))
                }))
        return out


@pytest.mark.parametrize("name", ["prefill_chunk", "decode_step", "_tower", "init_pools"])
def test_a_tower_takes_the_shared_functions_from_the_base(toy, name):
    # the base's own function object: no class between it and the tower
    # defines or overrides the name
    assert getattr(type(toy.model), name) is getattr(PagedTower, name)


@pytest.mark.parametrize("length", [BS - 1, BS + 1, 2 * BS])
def test_a_chunk_that_is_not_one_block_is_refused(toy, length):
    cache = _Cache(toy.model, toy.params, rows=1)
    with pytest.raises(ValueError, match="must equal block_size"):
        toy.model.prefill_chunk(
            toy.params, cache.pools, cache.call_tables(row=0),
            jnp.ones((1, length), jnp.int32), jnp.int32(0), block_size=BS,
        )


@pytest.mark.parametrize("pos", [5, 39], ids=["first-lap", "past-a-ring"])
def test_a_decode_step_writes_one_slot_a_live_row_and_the_null_block_for_an_idle_one(
    toy, pos
):
    """Row 0 lives at ``pos``, row 1 is idle at a position of its own: row
    0's new rows land in the block its kind's entry names (a plain kind's
    ``pos // block_size``, a window kind's the same modulo the ring's
    width) at slot ``pos % block_size``; row 1's land in ``NULL_BLOCK`` and
    no block of its tables changes."""
    cache = _Cache(toy.model, toy.params, rows=2)
    pools, logits, load = toy.model.decode_step(
        toy.params, cache.pools, cache.call_tables(), jnp.asarray([3, 5]),
        jnp.asarray([pos, 9]), block_size=BS,
        write_mask=jnp.asarray([True, False]),
    )
    assert np.all(np.isfinite(np.asarray(logits[0])))
    for kind, name, touched in cache.touched(pools):
        entry = pos // BS % cache.widths[kind] if cache.ring[kind] else pos // BS
        live = int(cache.tables[kind][0, entry])
        assert touched == {NULL_BLOCK: [9 % BS], live: [pos % BS]}, (kind, name)
        assert not set(touched) & set(cache.tables[kind][1].tolist())


@pytest.mark.parametrize("chunk", [1, 9], ids=["first-lap", "past-a-ring"])
def test_a_prefill_chunk_writes_the_block_its_kinds_entry_names(toy, chunk):
    cache = _Cache(toy.model, toy.params, rows=1)
    pools, logits, _ = toy.model.prefill_chunk(
        toy.params, cache.pools, cache.call_tables(row=0),
        jnp.ones((1, BS), jnp.int32), jnp.int32(chunk * BS), block_size=BS,
        last=jnp.int32(BS - 2),
    )
    assert logits.shape[0] == 1 and np.all(np.isfinite(np.asarray(logits)))
    for kind, name, touched in cache.touched(pools):
        entry = chunk % cache.widths[kind] if cache.ring[kind] else chunk
        block = int(cache.tables[kind][0, entry])
        # right-padding past ``last`` is written too: a whole block a call
        assert touched == {block: list(range(BS))}, (kind, name)
