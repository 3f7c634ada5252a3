"""Experts that received at least one pair in a routed layer of a decode
step, every expert held here: ``num_experts`` (256) -
``znicz_serve_moe_idle_experts_total{phase=decode}`` /
``znicz_serve_moe_layer_steps_total{phase=decode}``, over the window.  What
a step's bytes follow: an expert that is hit is read whole (6.29 MB)."""

from harness import laguna_readers as _shared


def read(obs):
    cfg = obs.get("config")
    if not _shared.is_ours(cfg):
        return None
    idle = obs["registry"].value("znicz_serve_moe_idle_experts_total", phase="decode")
    layer_steps = obs["registry"].value(
        "znicz_serve_moe_layer_steps_total", phase="decode"
    )
    if idle is None or not layer_steps:
        return None
    return cfg["num_experts"] - idle / layer_steps
