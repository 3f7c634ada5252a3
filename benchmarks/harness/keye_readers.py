"""What the per-layer readers of ``keye-vl2-30b-a3b-l6`` share: the decode
counters of the TRACED seconds as per-step means (``traced_registry``, the
registry's delta between the trace's start and stop: what a step reads
swings with the rows resident, so the window's mean does not describe the
traced seconds; ``drivers/serve_window_moe.TracedCapture``), and the decode
program's whole executions inside them (``harness/scoped_trace.py``).
Every function returns None where the program has no such counter or scope
(a parent commit), the configuration is another's, or the run traced
nothing."""

from harness import keye_work as work
from harness.axk1_readers import traced_decode


def decode_means(obs):
    reg, cfg = obs.get("traced_registry"), obs.get("config")
    if reg is None or not cfg or "sa_config" not in cfg:
        return None
    steps = reg.value("znicz_serve_decode_steps_total")
    layer_steps = reg.value("znicz_serve_moe_layer_steps_total", phase="decode")
    read = {
        "scored": reg.value("znicz_serve_sparse_keys_scored_total", phase="decode"),
        "selected": reg.value("znicz_serve_sparse_keys_selected_total", phase="decode"),
        "pairs": reg.value("znicz_serve_moe_pairs_total", phase="decode"),
        "idle": reg.value("znicz_serve_moe_idle_experts_total", phase="decode"),
    }
    if not steps or not layer_steps or None in read.values():
        return None
    return {
        "cfg": cfg,
        # what ONE layer read in a step
        "scored": read["scored"] / steps, "selected": read["selected"] / steps,
        "experts_hit": cfg["num_experts"] - read["idle"] / layer_steps,
        "pairs": read["pairs"] / layer_steps,
    }


def scope_seconds(obs, scope):
    """``(device seconds of the scope, decode steps)`` over the whole
    executions of the decode program in the trace."""
    traced = traced_decode(obs)
    if traced is None:
        return None
    seconds = traced["scopes"].get(scope)
    return (seconds, traced["steps"]) if seconds else None


def scope_roofline_pct(obs, scope, work_of):
    """100 x (least seconds of ``work_of(means)``, a layer and a step) x
    layers x steps / the scope's device seconds."""
    means, found = decode_means(obs), scope_seconds(obs, scope)
    if means is None or found is None or not obs.get("peaks"):
        return None
    seconds, steps = found
    layers = means["cfg"]["num_hidden_layers"]
    least = work.least_seconds(work_of(means), obs["peaks"]) * layers * steps
    return 100.0 * least / seconds
