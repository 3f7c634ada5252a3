"""Mean (token, choice) pairs an expert held here computes in one routed
layer of one decode step: ``znicz_serve_moe_pairs_total{phase=decode}`` /
(``znicz_serve_moe_layer_steps_total{phase=decode}`` x experts held).  How
near the batch is to what an expert sees in the deployment."""


def read(obs):
    pairs = obs["registry"].value("znicz_serve_moe_pairs_total", phase="decode")
    layer_steps = obs["registry"].value(
        "znicz_serve_moe_layer_steps_total", phase="decode"
    )
    held = (obs.get("config") or {}).get("n_routed_experts")
    if pairs is None or not layer_steps or not held:
        return None
    return pairs / (layer_steps * held)
