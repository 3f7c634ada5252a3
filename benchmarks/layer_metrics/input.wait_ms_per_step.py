"""Time a training step waited for its batch: the sum of
``znicz_prefetch_wait_seconds`` over the window / steps in the window."""


def read(obs):
    waited = obs["registry"].hist("znicz_prefetch_wait_seconds")
    if waited is None or not obs.get("steps"):
        return None
    return 1e3 * waited["sum"] / obs["steps"]
