"""What the parts of one layer's selecting attention cost in a decode step
and a prefill chunk at ``keye-vl2-30b-a3b-l6``'s sizes, each alone on the
chip over random pools (``chiprun -- python3 benchmarks/tools/probe_keye
_decode.py [live rows] [keys a row]``): the indexer's scores, the exact
selection, the fetch of the kept rows with its two products (the form that
runs), and, beside it, the walk of every block under the mask that the
latent towers' decode step uses.  One JSON line a part: milliseconds, the
median of 10 calls after one warm call.  PERF.md section 6 (PR 41) has the
readings the decode form was chosen by."""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from harness import loading  # noqa: E402
from znicz_tpu.ops import attention as att  # noqa: E402
from znicz_tpu.ops.pallas.latent_attention import latent_decode_attention  # noqa: E402


def timed(fn, *args):
    out = fn(*args)
    jax.block_until_ready(out)
    laps = []
    for _ in range(10):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        laps.append(1e3 * (time.perf_counter() - t0))
    return float(np.median(laps)), out


def main() -> None:
    live = int(sys.argv[1]) if len(sys.argv) > 1 else 48
    keys = int(sys.argv[2]) if len(sys.argv) > 2 else 66000
    cfg = loading.load_json("configs", "keye-vl2-30b-a3b-l6.json")
    sv, sa = cfg["serving"], cfg["sa_config"]
    slots, bs, m = sv["slots"], sv["block_size"], sv["max_seq"] // sv["block_size"]
    n, top_k = sv["n_blocks"]["global"], sa["topk"]
    heads, groups, dim = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    j = sa["indexer_num_heads"]
    key = jax.random.key(0)
    ks = jax.random.split(key, 8)
    pool = jax.random.normal(ks[0], (n, bs, 2 * groups * dim), jnp.bfloat16)
    idx_pool = jax.random.normal(ks[1], (n, bs, 128), jnp.bfloat16)
    # every row shares the first 512 blocks, as the cell's traffic does
    shared = jnp.arange(1, 513, dtype=jnp.int32)
    own = 513 + jnp.arange(slots * (m - 512), dtype=jnp.int32).reshape(slots, -1) % (n - 513)
    table = jnp.concatenate([jnp.broadcast_to(shared, (slots, 512)), own], axis=1)
    lengths = jnp.where(jnp.arange(slots) < live, keys, 0).astype(jnp.int32)
    pos = jnp.maximum(lengths - 1, 0)[:, None]
    q = jax.random.normal(ks[2], (slots, 1, heads, dim), jnp.float32)
    q_idx = jax.random.normal(ks[3], (slots, 1, j, 128), jnp.float32)
    w_idx = jax.random.normal(ks[4], (slots, 1, j), jnp.float32)
    args = dict(block_size=bs)

    def line(part, ms, **more):
        print(json.dumps({"part": part, "ms": round(ms, 3), "live": live,
                          "keys": keys, **more}), flush=True)

    scores_fn = jax.jit(lambda: att.paged_index_scores(
        q_idx, w_idx, idx_pool, table, pos, lengths=lengths, **args))
    ms, scores = timed(scores_fn)
    line("decode.dsa_indexer", ms)
    select_fn = jax.jit(lambda s: att.select_top_keys(s, top_k))
    ms, keep = timed(select_fn, scores)
    line("decode.dsa_select", ms, kept=int(keep.sum()))
    slots_fn = jax.jit(lambda k: att.kept_key_slots(k[:, 0], top_k, block_size=bs))
    ms, _ = timed(slots_fn, keep)
    line("decode.kept_key_slots", ms)
    fetch_fn = jax.jit(lambda k: att.kept_gqa_attention(
        q, pool, table, pos, k, n_kv_heads=groups, top_k=top_k,
        scale=dim ** -0.5, lengths=lengths, **args))
    ms, fetched = timed(fetch_fn, keep)
    line("decode.gqa_sparse_fetch", ms)

    def walk(k):
        q_row = att._gqa_query_rows(q, groups, pool.dtype)
        o = latent_decode_attention(
            q_row, pool, table, lengths, scale=dim ** -0.5,
            d_out=groups * dim, keep=k[:, 0],
        ).astype(jnp.float32)
        return att._gqa_own_values(o[:, None], groups, dim)

    ms, walked = timed(jax.jit(walk), keep)
    rows = np.asarray(lengths) > 0
    line("decode.masked_walk_of_every_block", ms,
         differs=float(jnp.max(jnp.abs(walked - fetched)[rows])))
    # a prefill chunk: 128 queries of one row at the row's end
    c = bs
    first = (keys - 1) // c * c
    pos1 = (first + jnp.arange(c))[None, :]
    q1 = jax.random.normal(ks[5], (1, c, heads, dim), jnp.float32)
    qi1 = jax.random.normal(ks[6], (1, c, j, 128), jnp.float32)
    wi1 = jax.random.normal(ks[7], (1, c, j), jnp.float32)
    ms, s1 = timed(jax.jit(lambda: att.paged_index_scores(
        qi1, wi1, idx_pool, table[:1], pos1, **args)))
    line("prefill.dsa_indexer", ms)
    ms, keep1 = timed(select_fn, s1)
    line("prefill.dsa_select", ms)
    ms, _ = timed(jax.jit(lambda k: att.kept_gqa_attention(
        q1, pool, table[:1], pos1, k, n_kv_heads=groups, top_k=top_k,
        scale=dim ** -0.5, **args)), keep1)
    line("prefill.gqa_sparse_walk", ms)


if __name__ == "__main__":
    main()
