"""Host time the training loop spends per step outside the epoch's
metric sync: the workflow timer's phases other than ``metrics_sync``
(``znicz_train_phase_seconds``), summed over the window / steps."""


def read(obs):
    phases = obs.get("train_phases")
    if not phases or not obs.get("steps"):
        return None
    busy = sum(p["sum"] for name, p in phases.items() if name != "metrics_sync")
    return 1e3 * busy / obs["steps"]
