"""The front door's own work in one turn of the serving thread: the
``frontdoor/*`` stages of ``znicz_serve_loop_seconds`` (control, pump,
stream, housekeeping) summed over the window / the turns that had work
(``znicz_serve_loop_iteration_seconds`` count)."""

from harness import serving_loop


def read(obs):
    spent = serving_loop.seconds(obs, serving_loop.FRONTDOOR)
    turns = obs["registry"].hist(serving_loop.TURNS)
    if spent is None or turns is None:
        return None
    return 1e3 * spent / turns["count"]
