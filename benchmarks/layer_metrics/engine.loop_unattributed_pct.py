"""Share of the serving thread's turns that none of its stages covers:
100 x (1 - every stage of ``znicz_serve_loop_seconds`` /
``znicz_serve_loop_iteration_seconds`` sum), over the window.  The stages
tile a turn, so anything but zero says a stage was skipped."""

from harness import serving_loop


def read(obs):
    turns = obs["registry"].hist(serving_loop.TURNS)
    staged = obs["registry"].hist(serving_loop.STAGES)
    if turns is None or staged is None or turns["sum"] <= 0:
        return None
    return 100.0 * (1.0 - staged["sum"] / turns["sum"])
