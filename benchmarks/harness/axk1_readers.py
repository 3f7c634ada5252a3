"""What the three roofline readers of ``axk1-ep16`` share: the window's
decode counters as per-step means, and the decode program's whole
executions inside the traced part of the window (``harness/
scoped_trace.py``)."""

from harness import axk1_work


def decode_means(obs):
    """None where the program has no such counters (a parent commit)."""
    reg, cfg = obs["registry"], obs.get("config")
    steps = reg.value("znicz_serve_decode_steps_total")
    gathered = reg.value("znicz_serve_decode_gathered_tokens_total")
    pairs = reg.value("znicz_serve_moe_pairs_total", phase="decode")
    idle = reg.value("znicz_serve_moe_idle_experts_total", phase="decode")
    layer_steps = reg.value("znicz_serve_moe_layer_steps_total", phase="decode")
    if not cfg or not steps or not layer_steps or pairs is None:
        return None
    slots = cfg["serving"]["slots"]
    return {
        "cfg": cfg,
        "rows_gathered_per_step": gathered / steps,  # slots x window, a layer
        "keys_per_row": gathered / steps / slots,
        "experts_hit_per_layer": cfg["n_routed_experts"] - idle / layer_steps,
        "pairs_per_layer": pairs / layer_steps,
    }


def traced_decode(obs):
    """The decode program's entry of the scope table, where the trace
    holds a whole execution of it."""
    entry = (obs.get("scoped") or {}).get(obs.get("decode_program"))
    if not entry or not entry["whole_executions"] or not entry["steps"]:
        return None
    return entry


def scope_roofline_pct(obs, scope, work_of):
    """100 x (least seconds of ``work_of(means)``, a layer and a step) x
    layers x steps / the scope's device seconds, over the whole
    executions of the decode program in the trace."""
    means, traced = decode_means(obs), traced_decode(obs)
    if means is None or traced is None or not obs.get("peaks"):
        return None
    seconds = traced["scopes"].get(scope)
    if not seconds:
        return None
    work, layers = work_of(means)
    least = axk1_work.least_seconds(work, obs["peaks"]) * layers * traced["steps"]
    return 100.0 * least / seconds
