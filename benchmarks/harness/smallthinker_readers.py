"""What the roofline readers of ``smallthinker-21b-l8`` share: the decode
counters of the TRACED seconds as per-step means (``traced_registry``, the
registry's delta between the trace's start and stop: what a step reads
swings with the rows resident, so the window's mean does not describe the
traced seconds), and the decode program's whole executions inside them
(``harness/scoped_trace.py``)."""

from harness import smallthinker_work as work
from harness.axk1_readers import traced_decode


def decode_means(obs):
    """None where the program has no such counters (a parent commit) or
    the run traced nothing."""
    reg, cfg = obs.get("traced_registry"), obs.get("config")
    if reg is None:
        return None
    steps = reg.value("znicz_serve_decode_steps_total")
    rows = {
        kind: reg.value("znicz_serve_decode_cached_rows_total", kind=kind)
        for kind in ("global", "window")
    }
    pairs = reg.value("znicz_serve_moe_pairs_total", phase="decode")
    idle = reg.value("znicz_serve_moe_idle_experts_total", phase="decode")
    layer_steps = reg.value("znicz_serve_moe_layer_steps_total", phase="decode")
    if (
        not cfg or "moe_num_primary_experts" not in cfg or not steps
        or not layer_steps or pairs is None or None in rows.values()
    ):
        return None
    return {
        "cfg": cfg,
        # cached rows ONE layer of the kind read in a step
        "rows_read_per_step": {k: v / steps for k, v in rows.items()},
        "experts_hit_per_layer": cfg["moe_num_primary_experts"] - idle / layer_steps,
        "pairs_per_layer": pairs / layer_steps,
    }


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
    job, layers = work_of(means)
    least = work.least_seconds(job, obs["peaks"]) * layers * traced["steps"]
    return 100.0 * least / seconds


def attention_roofline_pct(obs, kind):
    def work_of(means):
        cfg = means["cfg"]
        return (
            work.gqa_attention(cfg, means["rows_read_per_step"][kind]),
            work.layers_of(cfg)[kind],
        )

    return scope_roofline_pct(obs, "attn_" + kind, work_of)
