"""The decode step of attention over the rows a selection kept, read from
the pool in place (ops/pallas/kept_rows_attention.py, interpreted on the
CPU), against the gathered form of ``ops/attention.kept_gqa_attention`` on
one ``keep``: 3 to 6 slots, 8 query heads over 2 K/V heads of 32 (a ``[v,
k]`` row of 128 lanes), bfloat16 pools in blocks of 8 or 16, up to 24 keys
kept, taken 8 a piece and 2 a chunk so that a row's keys span pieces, a
class of rows spans chunks and a piece's copies start in several turns.

Tolerance: both forms multiply bfloat16 operands into float32 sums; the
gathered form rounds the normalised probabilities, the kernel rounds them
under the running maximum and divides at the end, which moves a result of
size ~1 by ~3e-3.  A wrong row, the wrong half of a packed pair or a key
left out moves it by 1e-1 and more."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from znicz_tpu import observability
from znicz_tpu.ops import attention as att
from znicz_tpu.ops.pallas import kept_rows_attention as kra
from znicz_tpu.workflow import sparse_gqa_lm as sgl

G, D, H = 2, 32, 8
TOL = dict(rtol=1e-2, atol=1e-2)


@pytest.fixture
def in_place(monkeypatch):
    """The form the TPU runs, interpreted, a few keys a piece."""
    monkeypatch.setattr(att, "_reads_pool_in_place", lambda tq: tq == 1)
    monkeypatch.setattr(kra, "PIECE_KEYS", 8)
    monkeypatch.setattr(kra, "CHUNK_KEYS", 2)
    # a piece's copies start a pair at a time, a pair between two chunks
    monkeypatch.setattr(kra, "UNROLL", 2)
    monkeypatch.setattr(kra, "AHEAD", 1)


def _pool(rng, n_blocks, bs):
    return jnp.asarray(
        rng.standard_normal((n_blocks, bs, 2 * G * D)), jnp.bfloat16
    )


def _both(pool, table, lengths, keep, *, bs, top_k, seed=0):
    """(in place, gathered) on the same keep; call under ``in_place``."""
    rng = np.random.default_rng(seed)
    b = len(lengths)
    q = jnp.asarray(rng.standard_normal((b, 1, H, D)), jnp.float32)
    lengths = jnp.asarray(lengths, jnp.int32)
    args = (
        q, pool, jnp.asarray(table, jnp.int32),
        jnp.maximum(lengths - 1, 0)[:, None], jnp.asarray(keep)[:, None],
    )
    kw = dict(
        block_size=bs, n_kv_heads=G, top_k=top_k, scale=D ** -0.5,
        lengths=lengths,
    )
    got = att.kept_gqa_attention(*args, **kw)
    with pytest.MonkeyPatch.context() as off:
        off.setattr(att, "_reads_pool_in_place", lambda tq: False)
        want = att.kept_gqa_attention(*args, **kw)
    return np.asarray(got), np.asarray(want)


def _keep(rng, lengths, n_keys, kept):
    keep = np.zeros((len(lengths), n_keys), bool)
    for b, (n, k) in enumerate(zip(lengths, kept)):
        if k:
            keep[b, rng.choice(n, k, replace=False)] = True
    return keep


# (lengths, keys kept): which slots live, and how many keys each names
LIVE = {
    "idle_slots_between_live_ones": ([40, 0, 0, 33, 0, 48], [24, 0, 0, 24, 0, 24]),
    "the_first_and_the_last_slot_alone": ([48, 0, 0, 41], [24, 0, 0, 20]),
    "only_a_middle_slot": ([0, 45, 0], [0, 24, 0]),
    "rows_shorter_than_top_k": ([5, 17, 48, 1], [5, 17, 24, 1]),
    "a_live_row_that_names_no_key": ([30, 9, 48], [24, 0, 7]),
    "one_key_named": ([30, 2, 0], [1, 1, 0]),
    "every_slot_idle": ([0, 0, 0], [0, 0, 0]),
}


@pytest.mark.parametrize("case", list(LIVE))
def test_the_kernel_attends_what_the_gathered_form_attends(in_place, case):
    lengths, kept = LIVE[case]
    rng = np.random.default_rng(len(case))
    b, bs, m = len(lengths), 8, 6
    pool = _pool(rng, b * m + 1, bs)
    table = 1 + rng.permutation(b * m).reshape(b, m)
    keep = _keep(rng, lengths, m * bs, kept)
    got, want = _both(pool, table, lengths, keep, bs=bs, top_k=24)
    np.testing.assert_allclose(got, want, **TOL)
    for slot, k in enumerate(kept):
        if not k:  # a slot that names no key: zeros
            assert not got[slot].any()
        else:
            assert np.abs(got[slot]).max() > 1e-2


@pytest.mark.parametrize("offsets", ["even", "odd", "one_class", "every"])
@pytest.mark.parametrize("bs", [8, 16])
def test_both_halves_of_a_packed_pair_of_rows_are_told_apart(
    in_place, offsets, bs
):
    """Rows 2i and 2i + 1 of a block share 32-bit words: a key's half is
    taken by its place in the buffer."""
    rng = np.random.default_rng(bs)
    b, m = 3, 48 // bs
    pool = _pool(rng, b * m + 2, bs)
    table = 1 + rng.permutation(b * m).reshape(b, m)
    at = np.arange(m * bs)
    allowed = {
        "even": at % 2 == 0, "odd": at % 2 == 1, "one_class": at % 8 == 5,
        "every": at >= 0,
    }[offsets]
    keep = np.zeros((b, m * bs), bool)
    for slot in range(b):
        keep[slot, rng.choice(at[allowed], min(20, allowed.sum()), replace=False)] = True
    got, want = _both(pool, table, [m * bs] * b, keep, bs=bs, top_k=24)
    np.testing.assert_allclose(got, want, **TOL)


def test_keys_in_blocks_that_rows_share_and_in_a_row_s_own_tail(in_place):
    rng = np.random.default_rng(7)
    bs, b = 8, 4
    pool = _pool(rng, 16, bs)
    # four shared blocks, then two of a row's own; the last one part filled
    table = np.stack([[1, 2, 3, 4, 5 + 2 * r, 6 + 2 * r] for r in range(b)])
    lengths = [43, 48, 0, 41]
    keep = np.zeros((b, 48), bool)
    for slot, n in enumerate(lengths):
        if n:
            keep[slot, rng.choice(32, 14, replace=False)] = True  # shared
            keep[slot, 32 + rng.choice(n - 32, 6, replace=False)] = True
    got, want = _both(pool, table, lengths, keep, bs=bs, top_k=24)
    np.testing.assert_allclose(got, want, **TOL)
    # the same shared keys, read through another row's table: another result
    assert np.abs(got[0] - got[1]).max() > 1e-2


def test_nothing_unnamed_is_read_into_the_result(in_place):
    """Every row of the pool that no live slot names holds NaN, the other
    seven rows of a fetched slab among them (the gathered form points its
    unnamed slots at the null block's first row, which stays finite)."""
    rng = np.random.default_rng(11)
    bs, b, m = 8, 4, 6
    lengths, kept = [48, 0, 19, 30], [24, 0, 3, 11]
    table = 1 + rng.permutation(b * m).reshape(b, m)
    keep = _keep(rng, lengths, m * bs, kept)
    keep[1, :8] = True  # an idle slot's mask is not read either
    named = np.zeros((b * m + 1) * bs, bool)
    named[0] = True
    for slot in (0, 2, 3):
        at = np.flatnonzero(keep[slot])
        named[table[slot][at // bs] * bs + at % bs] = True
    pool = np.asarray(_pool(rng, b * m + 1, bs), np.float32)
    pool = jnp.asarray(
        np.where(named.reshape(-1, bs)[..., None], pool, np.nan), jnp.bfloat16
    )
    got, want = _both(pool, table, lengths, keep, bs=bs, top_k=24)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, **TOL)


def test_a_pool_the_kernel_cannot_read_is_gathered(in_place):
    rng = np.random.default_rng(13)
    lengths = [20, 9]
    keep = _keep(rng, lengths, 24, [12, 9])
    table = 1 + rng.permutation(12).reshape(2, 6)
    narrow = jnp.asarray(rng.standard_normal((13, 4, 2 * G * D)), jnp.bfloat16)
    wide = jnp.asarray(rng.standard_normal((14, 4, 2 * G * D)), jnp.float32)
    assert kra.fetchable(_pool(rng, 14, 4))  # 56 rows of 128 bfloat16 lanes
    for pool in (narrow, wide):  # 52 rows: no whole slabs; 32-bit rows
        assert not kra.fetchable(pool)
        got, want = _both(pool, table, lengths, keep, bs=4, top_k=12)
        np.testing.assert_array_equal(got, want)
        with pytest.raises(ValueError, match="cannot be read a slab"):
            kra.kept_rows_decode_attention(
                jnp.zeros((2, H, 2 * G * D), pool.dtype), pool,
                jnp.zeros((2, 12), jnp.int32), jnp.zeros((2,), jnp.int32),
                scale=1.0, d_out=128,
            )


def test_the_places_put_each_class_of_rows_side_by_side():
    rows = jnp.asarray(
        [[3, 8, 11, 16, 21, 27, 0, 0], [5, 13, 6, 0, 0, 0, 0, 0]], jnp.int32
    )
    places, first, size = kra._places(rows, jnp.asarray([6, 3], jnp.int32), 4)
    places = np.asarray(places).reshape(2, 8)
    first, size = (np.asarray(a).reshape(2, 2, 8) for a in (first, size))
    slab, place = places >> kra.PLACE_BITS, places & (2 ** kra.PLACE_BITS - 1)
    # slot 0, piece 0: rows 3, 8, 11, 16 = classes 3, 0, 3, 0
    np.testing.assert_array_equal(slab[0], [0, 1, 1, 2, 2, 3, 0, 0])
    np.testing.assert_array_equal(place[0, :4], [2, 0, 3, 1])
    np.testing.assert_array_equal(size[0, 0], [2, 0, 0, 2, 0, 0, 0, 0])
    np.testing.assert_array_equal(first[0, 0], [0, 2, 2, 2, 4, 4, 4, 4])
    # piece 1: rows 21, 27 = classes 5, 3; two slots unnamed
    np.testing.assert_array_equal(place[0, 4:6], [1, 0])
    np.testing.assert_array_equal(size[0, 1], [0, 0, 0, 1, 0, 1, 0, 0])
    assert not places[0, 6:].any() and not places[1, 3:].any()
    # slot 1: rows 5, 13 of class 5 in the keys' order, then row 6
    np.testing.assert_array_equal(place[1, :3], [0, 1, 2])
    assert not size[1, 1].any()


def _form_counts():
    series = observability.get_registry().snapshot().get(
        "znicz_serve_kept_rows_attention_total", {"series": []}
    )["series"]
    return {
        form: sum(s["value"] for s in series if s["labels"]["form"] == form)
        for form in ("in_place", "gathered")
    }


def _toy_decode_step(dtype):
    model = sgl.SparseGQAMoEModel(
        n_layers=3, n_heads=H, n_kv_heads=G, head_dim=D, index_n_heads=4,
        index_head_dim=8, index_topk=16, top_k=3, max_positions=96,
    )
    params = sgl.init_params(
        model, d_model=64, vocab=256, d_ff_expert=32, n_experts=16, seed=3,
        dtype=dtype,
    )
    rng = np.random.default_rng(5)
    pools = [
        {
            "kv": _pool(rng, 19, 8).astype(dtype),
            "idx": jnp.asarray(rng.standard_normal(p["idx"].shape), dtype),
        }
        for p in model.init_pools(params, {"global": 19}, 8)
    ]
    tables = {"global": jnp.asarray(1 + rng.permutation(18).reshape(3, 6), jnp.int32)}
    token = jnp.asarray([7, 9, 11], jnp.int32)
    pos = jnp.asarray([40, 13, 29], jnp.int32)
    mask = jnp.asarray([True, True, False])

    def step():
        return jax.jit(model.decode_step, static_argnames=("block_size",))(
            params, pools, tables, token, pos, block_size=8, write_mask=mask
        )

    return step


def test_a_decode_program_counts_the_form_it_was_built_with_once_a_layer(
    monkeypatch,
):
    step = _toy_decode_step(jnp.bfloat16)
    before = _form_counts()
    _, gathered, load = step()
    after = _form_counts()
    assert after["gathered"] - before["gathered"] == 3  # off the TPU
    assert after["in_place"] == before["in_place"]
    monkeypatch.setattr(att, "_reads_pool_in_place", lambda tq: tq == 1)
    _, in_place, load_in_place = step()
    built = _form_counts()
    assert built["in_place"] - after["in_place"] == 3
    assert built["gathered"] == after["gathered"]
    # the same step: the logits of the rows that decode, and what it counts
    np.testing.assert_allclose(
        np.asarray(in_place)[:2], np.asarray(gathered)[:2], rtol=5e-2, atol=5e-2
    )
    assert int(load_in_place["cached_rows"]) == int(load["cached_rows"]) == 16 + 14
    # float32 pools are read by the gathered form wherever the program runs
    _toy_decode_step(jnp.float32)()
    assert _form_counts()["gathered"] - built["gathered"] == 3
