"""Mean wall time of one prefill chunk call in the engine:
``znicz_serve_phase_seconds{phase=prefill}`` and ``{phase=admit}`` (the
prompt's last chunk) sum / count over the window."""


def read(obs):
    parts = [
        obs["registry"].hist("znicz_serve_phase_seconds", phase=p)
        for p in ("prefill", "admit")
    ]
    parts = [p for p in parts if p]
    if not parts:
        return None
    return 1e3 * sum(p["sum"] for p in parts) / sum(p["count"] for p in parts)
