"""Mean time a request waits in the front door before the engine takes
it: the front door's submit-to-first-token (``znicz_serve_frontdoor_ttft_
seconds``, sum / count) less the engine's own
(``znicz_serve_ttft_seconds``), both exact sums over the window.
(``znicz_serve_frontdoor_queue_age_seconds`` is a gauge of the oldest
queued request and gives no mean; PERF.md, Open questions.)"""


def read(obs):
    door = obs["registry"].hist("znicz_serve_frontdoor_ttft_seconds")
    engine = obs["registry"].hist("znicz_serve_ttft_seconds")
    if door is None or engine is None:
        return None
    return 1e3 * (door["sum"] / door["count"] - engine["sum"] / engine["count"])
