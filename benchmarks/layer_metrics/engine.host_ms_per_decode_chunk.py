"""The engine's own host work, a decode (or verify) chunk: every
``serve/*`` stage of ``znicz_serve_loop_seconds`` that is not a wait
(schedule, a prefill chunk's host part, grow, prepare, dispatch, fetch,
emit, the drafter) summed over the window / the chunks
(``harness/serving_loop.py``)."""

from harness import serving_loop


def read(obs):
    return serving_loop.ms_per_decode_chunk(obs, serving_loop.ENGINE_HOST)
