#!/usr/bin/env python3
"""What a decode step's selection costs by the rows it visits, alone on the
chip at ``keye-serve-shared-long-context``'s sizes (64 slots, 544 blocks of
128 keys, 2,048 kept): ``chiprun -- python3 tools/probe_select_rows.py
[live rows] [keys a row]``.

Each form runs ``CALLS`` times inside ONE compiled program (a call's scores
depend on the call before, so nothing is hoisted or shared) and the line
gives milliseconds a call, the median of 10 runs after a warm one:

- ``whole_batch``: ``select_top_keys`` + ``kept_row_addresses`` over every
  slot, live or not (the form before PR 45, and the tests' oracle);
- ``live_rows.R``: ``select_live_rows`` with tiles of ``R`` rows, choosing
  and listing (the grouped-query tower's decode step);
- ``live_rows_mask.R``: the same without the listing (the latent tower's).

Every form's result is compared with ``whole_batch``'s: ``equal`` must be
true.  PERF.md section 6 (PR 45) has the readings."""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from znicz_tpu.ops import attention as att  # noqa: E402

SLOTS, BLOCK, TABLE, TOP_K, CALLS = 64, 128, 544, 2048, 12


def _timed(fn, *args):
    out = jax.block_until_ready(fn(*args))
    laps = []
    for _ in range(10):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        laps.append(1e3 * (time.perf_counter() - t0))
    return float(np.median(laps)) / CALLS, out


def _repeated(one_call):
    """``one_call(scores) -> (result, int32 scalar)`` run ``CALLS`` times,
    each on scores that wait for the call before."""

    def program(scores):
        def body(_, carry):
            _, total = carry
            out, n = one_call(scores + 0.0 * total.astype(jnp.float32))
            return out, total + n

        first, n = one_call(scores)
        return jax.lax.fori_loop(1, CALLS, body, (first, n))

    return jax.jit(program)


def main() -> None:
    live = int(sys.argv[1]) if len(sys.argv) > 1 else 24
    keys = int(sys.argv[2]) if len(sys.argv) > 2 else 66000
    rng = np.random.default_rng(0)
    # the live rows scattered among the idle ones, as slots are bound
    lengths = np.zeros(SLOTS, np.int32)
    lengths[rng.permutation(SLOTS)[:live]] = keys
    lengths = jnp.asarray(lengths)
    scores = jnp.where(
        jnp.arange(TABLE * BLOCK)[None, None, :] < lengths[:, None, None],
        jax.random.normal(jax.random.key(1), (SLOTS, 1, TABLE * BLOCK)),
        -jnp.inf,
    )
    table = jnp.asarray(
        rng.integers(1, 2048, (SLOTS, TABLE)).astype(np.int32)
    )

    def whole_batch(s):
        keep = att.select_top_keys(s, TOP_K)
        listed = att.kept_row_addresses(
            keep[:, 0], table, TOP_K, block_size=BLOCK
        )
        return (listed, keep), jnp.sum(keep, dtype=jnp.int32)

    def live_rows(s):
        listed, _, selected, _ = att.select_live_rows(
            s, lengths, TOP_K, block_table=table, block_size=BLOCK
        )
        return listed, selected

    def live_rows_mask(s):
        keep, _, selected, _ = att.select_live_rows(s, lengths, TOP_K)
        return keep, selected

    def line(part, ms, **more):
        print(json.dumps({"part": part, "ms_a_call": round(ms, 4),
                          "live": live, "keys": keys, **more}), flush=True)

    ms, ((want_listed, want_keep), _) = _timed(_repeated(whole_batch), scores)
    line("whole_batch", ms)
    for rows in (8, 16):
        att.SELECT_TILE_ROWS = rows
        ms, (listed, _) = _timed(_repeated(live_rows), scores)
        equal = all(
            bool(jnp.array_equal(a, b)) for a, b in zip(listed, want_listed)
        )
        line(f"live_rows.{rows}", ms, equal=equal)
        ms, (keep, _) = _timed(_repeated(live_rows_mask), scores)
        line(f"live_rows_mask.{rows}", ms,
             equal=bool(jnp.array_equal(keep, want_keep)))


if __name__ == "__main__":
    main()
