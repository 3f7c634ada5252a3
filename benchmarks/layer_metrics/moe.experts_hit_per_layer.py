"""Experts that received at least one pair in a layer of a decode step,
every expert held here: 64 - ``znicz_serve_moe_idle_experts_total{phase=
decode}`` / ``znicz_serve_moe_layer_steps_total{phase=decode}``.  What a
step's bytes follow: an expert that is hit is read whole."""


def read(obs):
    idle = obs["registry"].value("znicz_serve_moe_idle_experts_total", phase="decode")
    layer_steps = obs["registry"].value(
        "znicz_serve_moe_layer_steps_total", phase="decode"
    )
    held = (obs.get("config") or {}).get("moe_num_primary_experts")
    if idle is None or not layer_steps or not held:
        return None
    return held - idle / layer_steps
