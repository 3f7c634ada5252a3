"""The native crop's part of the producer's fetch per training step:
``znicz_pipeline_stage_seconds{stage=crop}`` sum over the window / steps.
Absent where the loader cuts no crop on the host (a resident pool)."""


def read(obs):
    spent = obs["registry"].hist("znicz_pipeline_stage_seconds", stage="crop")
    if spent is None or not obs.get("steps"):
        return None
    return 1e3 * spent["sum"] / obs["steps"]
