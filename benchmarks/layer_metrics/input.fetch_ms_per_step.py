"""Producer time spent materialising a batch (loader, crop parameters,
the native crop, labels) per training step:
``znicz_pipeline_stage_seconds{stage=fetch}`` sum over the window / steps."""


def read(obs):
    spent = obs["registry"].hist("znicz_pipeline_stage_seconds", stage="fetch")
    if spent is None or not obs.get("steps"):
        return None
    return 1e3 * spent["sum"] / obs["steps"]
