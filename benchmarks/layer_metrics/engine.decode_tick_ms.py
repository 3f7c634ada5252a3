"""Mean wall time of one decode chunk (up to ``admit_every`` steps) as
the engine sees it, host work included:
``znicz_serve_phase_seconds{phase=decode}`` sum / count over the window."""


def read(obs):
    decode = obs["registry"].hist("znicz_serve_phase_seconds", phase="decode")
    if decode is None:
        return None
    return 1e3 * decode["sum"] / decode["count"]
