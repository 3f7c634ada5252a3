"""Host-to-device copy time per training step, from the ``device_put``
call to the arrays being ready on the device:
``znicz_pipeline_stage_seconds{stage=h2d_landed}`` sum over the window /
steps.  Taken by a watcher thread beside the producer's loop, so it is
not part of the producer's own total."""


def read(obs):
    spent = obs["registry"].hist(
        "znicz_pipeline_stage_seconds", stage="h2d_landed"
    )
    if spent is None or not obs.get("steps"):
        return None
    return 1e3 * spent["sum"] / obs["steps"]
