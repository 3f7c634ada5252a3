"""Mean time a request waited in the engine's own queue before a slot
took it: ``znicz_serve_engine_queue_wait_seconds`` sum / count over the
window (once an admission; a preempted request again when it is taken
back)."""


def read(obs):
    waited = obs["registry"].hist("znicz_serve_engine_queue_wait_seconds")
    if waited is None:
        return None
    return 1e3 * waited["sum"] / waited["count"]
