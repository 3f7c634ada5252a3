"""Share of the collective time in which no compute operation runs on
the first device: what the all-reduce adds to the step."""


def read(obs):
    trace = obs.get("trace")
    if not trace or not trace["collective_s"]:
        return None
    return 100.0 * trace["collective_exposed_s"] / trace["collective_s"]
