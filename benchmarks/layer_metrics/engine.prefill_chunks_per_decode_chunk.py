"""Prefill chunks dispatched between two decode (or verify) chunks, rows
decoding throughout: ``znicz_serve_prefill_chunks_between_decodes`` sum /
count over the window.  Each stands in front of the next decode chunk's
tokens for its whole device time."""


def read(obs):
    between = obs["registry"].hist("znicz_serve_prefill_chunks_between_decodes")
    if between is None:
        return None
    return between["sum"] / between["count"]
