"""Mean time a request waited in the front door before it was handed to
the engine: ``znicz_serve_frontdoor_queue_wait_seconds`` sum / count over
the window (observed once per request at the hand-over)."""


def read(obs):
    waited = obs["registry"].hist("znicz_serve_frontdoor_queue_wait_seconds")
    if waited is None:
        return None
    return 1e3 * waited["sum"] / waited["count"]
