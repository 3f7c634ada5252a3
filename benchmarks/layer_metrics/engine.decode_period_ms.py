"""Mean period between two decode (or verify) chunks' tokens, rows decoding
throughout: ``znicz_serve_decode_period_seconds`` sum / count over the
window (from the end of one chunk's wait for the device to the end of the
next one's).  Over the steps a chunk it is the engine's own time per
output token, which is what ``tpot_p95_ms`` is made of."""


def read(obs):
    period = obs["registry"].hist("znicz_serve_decode_period_seconds")
    if period is None:
        return None
    return 1e3 * period["sum"] / period["count"]
