"""Time the serving thread blocked on the device, a decode (or verify)
chunk: the stages ``serve/prefill/wait``, ``serve/decode/wait`` and
``serve/verify/wait`` of ``znicz_serve_loop_seconds`` summed over the
window / the chunks that waited (``harness/serving_loop.py``).  It holds
the decode program and every prefill chunk queued ahead of it."""

from harness import serving_loop


def read(obs):
    return serving_loop.ms_per_decode_chunk(obs, serving_loop.WAITS)
