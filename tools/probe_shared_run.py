#!/usr/bin/env python3
"""What one global layer's decode attention costs by how it reads a prefix
its live rows share, alone on the chip at ``laguna-serve-agent-turns``'
sizes (48 slots, 48 query rows of 2,048 lanes, a table of 176 blocks of
128 keys, a pool of 1,280): ``chiprun -- python3 tools/probe_shared_run.py
[smallthinker] [live rows ...]`` (7, 15 and 48 where none is given;
``smallthinker``: that tower's global layer instead, 64 slots, 32 query rows
of 1,024 lanes, a table of 128, where 2-3 rows decode and nothing is shared:
read the ``unshared.*`` lines).

Every live row opens with the same 96 blocks and goes on with 8-20 of its
own (13.3-14.8k keys); the live rows are scattered among the idle ones, as
slots are bound.  Each form runs ``CALLS`` times inside ONE compiled program
(a call's queries wait for the call before, so nothing is hoisted or
shared) and a line gives milliseconds a call, the median of 10 runs after a
warm one:

- ``per_row``: ``latent_decode_attention``, every row its whole table (the
  form before PR 47, and the window layers' still);
- ``shared.R[.keys]``: ``shared_run_decode_attention`` with tiles of ``R``
  rows; ``.keys``: the score product over the key half of the lanes alone
  (``q_from``);
- ``unshared.*``: both forms over tables with no two first blocks alike
  (every run 0): what the planning and the idle shared pass cost a tower
  that has nothing to share.  ``equal`` must be true there; elsewhere
  ``widest`` is the largest difference from ``per_row``'s result.

``fetched`` / ``attended``: the cached rows the form moves and the rows its
queries meet.  PERF.md section 6 (PR 47) has the readings."""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from znicz_tpu.ops.pallas import latent_attention as la  # noqa: E402

SLOTS, HEADS, LANES, BLOCK, TABLE, POOL = 48, 48, 2048, 128, 176, 1280
SHARED_BLOCKS, CALLS = 96, 8
OWN = (1024, 2560)  # keys of a row past the shared ones
SCALE, D_OUT = 128 ** -0.5, LANES // 2


def _timed(fn, *args):
    out = jax.block_until_ready(fn(*args))
    laps = []
    for _ in range(10):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        laps.append(1e3 * (time.perf_counter() - t0))
    return float(np.median(laps)) / CALLS, out


def _repeated(attend):
    """``attend(q)`` run ``CALLS`` times, each on queries that wait for the
    call before."""

    def program(q, pool, table, lengths):
        def body(_, o):
            wait = (0.0 * jnp.sum(o[:, :1, :1].astype(jnp.float32))).astype(q.dtype)
            return attend(q + wait, pool, table, lengths)

        return jax.lax.fori_loop(
            1, CALLS, body, attend(q, pool, table, lengths)
        )

    return jax.jit(program)


def _case(rng, live, shared):
    """``(table [SLOTS, TABLE], lengths [SLOTS])``: ``live`` rows of
    13.3-14.8k keys, the first ``shared`` blocks of every table the same."""
    lengths = np.zeros(SLOTS, np.int32)
    rows = rng.permutation(SLOTS)[:live]
    lengths[rows] = SHARED_BLOCKS * BLOCK + rng.integers(*OWN, live)
    table = np.zeros((SLOTS, TABLE), np.int32)  # NULL_BLOCK past a row's end
    for i, r in enumerate(rows):
        n = -(-int(lengths[r]) // BLOCK)
        # a row's own blocks: any of the pool's (what a block holds does
        # not matter here), its first one no other row's
        table[r, :n] = rng.integers(SHARED_BLOCKS + SLOTS + 1, POOL, n)
        table[r, 0] = SHARED_BLOCKS + 1 + i
        table[r, :shared] = np.arange(1, shared + 1)
    return jnp.asarray(table), jnp.asarray(lengths)


def main() -> None:
    global SLOTS, HEADS, LANES, TABLE, POOL, D_OUT
    args = sys.argv[1:]
    if args[:1] == ["smallthinker"]:
        args = args[1:]
        SLOTS, HEADS, LANES, TABLE, POOL = 64, 32, 1024, 128, 1600
        D_OUT = LANES // 2
    lives = [int(a) for a in args] or [7, 15, 48]
    rng = np.random.default_rng(0)
    key = jax.random.key(1)
    pool = jax.random.normal(key, (POOL, BLOCK, LANES), jnp.bfloat16)
    q = jax.random.normal(jax.random.fold_in(key, 1), (SLOTS, HEADS, LANES))
    # a grouped-query tower's queries: zeros over the value half
    q = q.at[:, :, :D_OUT].set(0.0).astype(jnp.bfloat16)

    def per_row(q, pool, table, lengths):
        return la.latent_decode_attention(
            q, pool, table, lengths, scale=SCALE, d_out=D_OUT
        )

    def shared_run(q_from):
        def attend(q, pool, table, lengths):
            return la.shared_run_decode_attention(
                q, pool, table, lengths, scale=SCALE, d_out=D_OUT,
                q_from=q_from,
            )

        return attend

    def line(part, live, ms, **more):
        print(json.dumps({"part": part, "live": live,
                          "ms_a_call": round(ms, 4), **more}), flush=True)

    forms = [
        (f"shared.{r}" + (".keys" if q_from else ""), r, q_from)
        for r in (8, 4, 16) for q_from in (0, D_OUT)
    ]
    for live in lives:
        for name, shared in (("", SHARED_BLOCKS), ("unshared.", 0)):
            if name and live != lives[len(lives) // 2]:
                continue
            table, lengths = _case(rng, live, shared)
            attended = int(jnp.sum(-(-lengths // BLOCK) * BLOCK))
            ms, want = _timed(_repeated(per_row), q, pool, table, lengths)
            line(name + "per_row", live, ms, fetched=attended,
                 attended=attended)
            for part, r, q_from in forms[: 2 if name else None]:
                la.TILE_ROWS = r
                ms, got = _timed(
                    _repeated(shared_run(q_from)), q, pool, table, lengths
                )
                fetched = int(la.shared_run_rows_fetched(
                    table, lengths, block_size=BLOCK
                ))
                gap = jnp.abs(
                    got.astype(jnp.float32) - want.astype(jnp.float32)
                )
                line(name + part, live, ms, fetched=fetched,
                     attended=attended, widest=float(jnp.max(gap)),
                     equal=bool(jnp.array_equal(got, want)))
    la.TILE_ROWS = 8


if __name__ == "__main__":
    main()
