"""A decode step that reads the blocks its live rows share once a tile of
rows (ops/pallas/latent_attention.py: ``shared_run_plan``, the shared pass
and the seeded own pass behind ``shared_run_decode_attention``), interpreted
on the CPU, against the kernel that walks every row's whole table
(``latent_decode_attention``): EQUAL where no tile has a run (the same
arithmetic in the same order), close where one has (a member meets its
chunks in the order it always did; what differs is how a product of
stacked rows is tiled), and the rows it fetches counted against a plan
written out as loops.

Small sizes: blocks of 4 keys, chunks of 2 blocks, tiles of 4 rows, 16
query rows of 256 lanes, the weighted sum over the leading 128."""

import jax.numpy as jnp
import numpy as np
import pytest

from znicz_tpu.ops import attention as att
from znicz_tpu.ops.pallas import latent_attention as la

BS, CHUNK, R, HEADS, LANES, D_OUT = 4, 2, 4, 16, 256, 128
TABLE, POOL = 14, 256
SCALE = LANES ** -0.5
CLOSE = dict(rtol=2e-5, atol=2e-5)


@pytest.fixture(autouse=True)
def small_tiles(monkeypatch):
    monkeypatch.setattr(la, "CHUNK_BLOCKS", CHUNK)
    monkeypatch.setattr(la, "TILE_ROWS", R)


def _case(rows, seed=0):
    """``rows``: a ``(prefix, shared, length)`` a slot: the row's first
    ``shared`` table entries are prefix ``prefix``'s blocks (a letter; None:
    the row shares nothing), the rest its own; ``length`` 0: the slot idles
    (its table names NULL_BLOCK).  Returns ``(q, pool, table, lengths)``."""
    rng = np.random.default_rng(seed)
    free = iter(rng.permutation(np.arange(1, POOL)))
    prefixes = {}
    table = np.zeros((len(rows), TABLE), np.int32)
    for r, (prefix, shared, length) in enumerate(rows):
        n = -(-length // BS)
        table[r, :n] = [next(free) for _ in range(n)]
        if prefix is not None:
            blocks = prefixes.setdefault(
                prefix, [next(free) for _ in range(TABLE)]
            )
            table[r, :shared] = blocks[:shared]
    pool = rng.standard_normal((POOL, BS, LANES)).astype(np.float32)
    q = rng.standard_normal((len(rows), HEADS, LANES)).astype(np.float32)
    lengths = np.asarray([length for _, _, length in rows], np.int32)
    return jnp.asarray(q), jnp.asarray(pool), jnp.asarray(table), jnp.asarray(lengths)


def _plan_by_loops(table, lengths):
    """``shared_run_plan`` as its docstring reads, row by row: ``(runs a
    tile in chunks, first chunk a slot, cached rows fetched, the order, the
    lead a tile)``."""
    table, lengths = np.asarray(table), np.asarray(lengths)
    b = len(lengths)
    order = sorted(
        range(b), key=lambda i: (table[i, 0] if lengths[i] else 2 ** 31, i)
    )
    runs, first_chunk, leads = [], [0] * b, []
    for t in range(0, b, R):
        tile = [i for i in order[t:t + R] if lengths[i]]
        share = {}
        leads.append(order[t])
        # the lead: whose first entry most of the tile's live rows have
        votes = [sum(table[j, 0] == table[i, 0] for j in tile) for i in tile]
        for i in tile:
            lead = leads[-1] = tile[votes.index(max(votes))]
            common = 0
            while common < TABLE and table[i, common] == table[lead, common]:
                common += 1
            chunks = min(common, (lengths[i] - 1) // BS) // CHUNK
            if chunks:
                share[i] = chunks
        runs.append(min(share.values()) if len(share) > 1 else 0)
        for i in share:
            first_chunk[i] = runs[-1]
    fetched = sum(runs) * CHUNK * BS + sum(
        (-(-int(n) // BS) - c * CHUNK) * BS
        for n, c in zip(lengths, first_chunk) if n
    )
    return runs, first_chunk, fetched, order, leads


# name -> (rows, the runs its tiles must have, in chunks)
CASES = {
    "no_two_tables_alike": (
        [(None, 0, 21), (None, 0, 40), (None, 0, 9), (None, 0, 33)], [0],
    ),
    "all_rows_one_prefix": (
        [("a", 8, 41), ("a", 8, 37), ("a", 8, 50), ("a", 8, 33)], [4],
    ),
    "two_prefixes_in_one_batch": (
        [("a", 8, 41), ("b", 6, 30), ("a", 8, 37), ("b", 6, 44),
         ("a", 8, 50), ("b", 6, 29), ("a", 8, 33), ("b", 6, 27)], [4, 3],
    ),
    "a_stray_row_of_another_prefix_inside_a_tile": (
        [("a", 8, 41), ("a", 8, 37), ("b", 6, 30), ("a", 8, 50)], [4],
    ),
    "a_stray_row_of_another_prefix_at_a_tiles_head": (
        [("a", 8, 41), ("a", 8, 37), ("b", 6, 30), ("a", 8, 50)], [4],
    ),
    "a_member_sharing_only_part_of_the_run": (
        [("a", 8, 41), ("a", 4, 37), ("a", 8, 50), ("a", 8, 35)], [2],
    ),
    "a_run_that_is_not_a_whole_number_of_chunks": (
        [("a", 7, 41), ("a", 7, 37), ("a", 7, 50), ("a", 7, 36)], [3],
    ),
    "a_row_whose_length_ends_one_key_past_the_run": (
        [("a", 8, 33), ("a", 8, 48), ("a", 8, 41)], [4],
    ),
    "a_row_too_short_for_the_run_cuts_it": (
        [("a", 8, 41), ("a", 8, 32), ("a", 8, 50)], [3],
    ),
    "a_row_shorter_than_a_chunk_is_no_member": (
        [("a", 8, 41), ("a", 1, 7), ("a", 8, 50)], [4],
    ),
    "live_rows_scattered_among_idle_ones": (
        [(None, 0, 0), ("a", 8, 41), (None, 0, 0), (None, 0, 0),
         ("a", 8, 37), (None, 0, 0), ("a", 8, 50), (None, 0, 0),
         (None, 0, 0), ("a", 8, 35), ("a", 8, 44), (None, 0, 0)], [4, 0, 0],
    ),
    "one_member_alone_has_no_run": (
        [("a", 8, 41), (None, 0, 22), (None, 0, 0), (None, 0, 17)], [0],
    ),
}
for live in (0, 1, R - 1, R, R + 1, 2 * R + 1):
    CASES[f"{live}_of_{2 * R + 1}_rows_live"] = (
        [("a", 8, 33 + 2 * i) if i < live else (None, 0, 0)
         for i in range(2 * R + 1)],
        [4 if live >= 2 else 0, 4 if live > R + 1 else 0, 0],
    )


@pytest.mark.parametrize("name", sorted(CASES))
def test_the_two_passes_against_every_row_its_whole_table(name):
    rows, want_runs = CASES[name]
    q, pool, table, lengths = _case(rows)
    if "stray" in name:  # the stray row sorts last in its tile, or first
        b_first = table[2, 0] < table[0, 0]
        if b_first != name.endswith("head"):
            table = table.at[2, 0].set(table[0, 0]).at[(0, 1, 3), 0].set(table[2, 0])
            pool = pool.at[table[2, 0]].set(pool[table[0, 0]]).at[table[0, 0]].set(pool[table[2, 0]])
        assert bool(table[2, 0] < table[0, 0]) == name.endswith("head")
    call = dict(scale=SCALE, d_out=D_OUT)
    whole = np.asarray(la.latent_decode_attention(q, pool, table, lengths, **call))
    got = np.asarray(
        la.shared_run_decode_attention(q, pool, table, lengths, **call)
    )
    runs, first_chunk, fetched, order, leads = _plan_by_loops(table, lengths)
    if want_runs is not None:  # which prefix's tile comes first is the draw's
        assert sorted(runs) == sorted(want_runs)
    plan = {
        k: list(np.asarray(v)) for k, v in la.shared_run_plan(
            table, lengths, block_size=BS, chunk_blocks=CHUNK, tile_rows=R
        ).items()
    }
    n, tiles = len(rows), len(runs)
    assert plan["run"] == runs and plan["first_chunk"] == first_chunk
    assert plan["rows"] == order + [n - 1] * (tiles * R - n)
    assert [plan["place"][i] for i in order] == list(range(n))
    assert [lead for lead, run in zip(plan["lead"], runs) if run] == [
        lead for lead, run in zip(leads, runs) if run
    ]
    assert plan["next_run"] == [
        min([j for j in range(t + 1, tiles) if runs[j]], default=tiles)
        for t in range(tiles)
    ]
    assert plan["next_live"] == list(np.asarray(la.next_live_slot(lengths)))
    if any(runs):
        np.testing.assert_allclose(got, whole, **CLOSE)
    else:
        np.testing.assert_array_equal(got, whole)
    assert not got[np.asarray(lengths) == 0].any()  # idle rows give zeros
    assert int(la.shared_run_rows_fetched(table, lengths, block_size=BS)) == fetched
    attended = int(np.sum(-(-np.asarray(lengths) // BS) * BS))
    assert (fetched < attended) == any(runs) and fetched <= attended


@pytest.mark.parametrize("q_from", [0, D_OUT])
def test_the_score_product_may_skip_lanes_that_are_zero_in_every_query(q_from):
    rows, _ = CASES["two_prefixes_in_one_batch"]
    q, pool, table, lengths = _case(rows, seed=3)
    q = q.at[:, :, :D_OUT].set(0.0)  # a grouped-query tower's value half
    call = dict(scale=SCALE, d_out=D_OUT)
    np.testing.assert_allclose(
        la.shared_run_decode_attention(
            q, pool, table, lengths, q_from=q_from, **call
        ),
        la.latent_decode_attention(q, pool, table, lengths, **call), **CLOSE,
    )


@pytest.mark.parametrize(
    "name", ["no_two_tables_alike", "two_prefixes_in_one_batch",
             "live_rows_scattered_among_idle_ones"],
)
def test_a_global_layers_decode_step_is_the_gathered_form(name, monkeypatch):
    """``paged_gqa_attention`` on the TPU's path (the kernels interpreted)
    against the gathered form, and what each form counts as read."""
    rows, _ = CASES[name]
    _, pool, table, lengths = _case(rows)
    rng = np.random.default_rng(5)
    q = jnp.asarray(
        rng.standard_normal((len(rows), 1, 12, 64)).astype(np.float32)
    )
    live = np.asarray(lengths) > 0

    def attend():
        return np.asarray(att.paged_gqa_attention(
            q, pool, table, jnp.maximum(lengths - 1, 0)[:, None],
            block_size=BS, n_kv_heads=2, lengths=lengths,
        ))

    def counts():
        return [
            int(f(table, lengths, block_size=BS))
            for f in (att.paged_gqa_rows_read, att.paged_gqa_rows_attended)
        ]

    gathered = attend()
    assert counts() == [table.size * BS] * 2
    calls = []
    for form in ("latent_decode_attention", "shared_run_decode_attention"):
        kernel = getattr(att, form)
        monkeypatch.setattr(
            att, form,
            lambda *a, _f=form, _k=kernel, **kw: calls.append(_f) or _k(*a, **kw),
        )
    monkeypatch.setattr(att, "_reads_pool_in_place", lambda tq: tq == 1)
    in_place = attend()
    assert calls == ["shared_run_decode_attention"]
    np.testing.assert_allclose(in_place[live], gathered[live], rtol=2e-4, atol=2e-4)
    assert not in_place[~live].any()
    fetched = _plan_by_loops(table, lengths)[2]
    assert counts() == [fetched, int(np.sum(-(-np.asarray(lengths) // BS) * BS))]
