"""Time a training step waited for its batch in the middle of an epoch,
where the producer could have been a batch ahead:
``znicz_prefetch_wait_seconds{at=steady}`` sum over the window / steps."""


def read(obs):
    waited = obs["registry"].hist("znicz_prefetch_wait_seconds", at="steady")
    if waited is None or not obs.get("steps"):
        return None
    return 1e3 * waited["sum"] / obs["steps"]
