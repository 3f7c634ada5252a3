"""The busiest held expert's pairs over the mean expert's, per routed
layer and decode step, averaged over the window:
``znicz_serve_moe_busiest_pairs_total`` x experts held /
``znicz_serve_moe_pairs_total`` (both ``phase=decode``).  1 is an even
load; the grouped product's time follows the busiest."""


def read(obs):
    pairs = obs["registry"].value("znicz_serve_moe_pairs_total", phase="decode")
    busiest = obs["registry"].value(
        "znicz_serve_moe_busiest_pairs_total", phase="decode"
    )
    held = (obs.get("config") or {}).get("n_routed_experts")
    if busiest is None or not pairs or not held:
        return None
    return busiest * held / pairs
