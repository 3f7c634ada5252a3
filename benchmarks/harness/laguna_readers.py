"""What the per-layer readers of ``laguna-xs2-stage1`` share: the decode
counters of the TRACED seconds as per-step means (``traced_registry``, the
registry's delta between the trace's start and stop: what a step reads
swings with the rows resident, so the window's mean does not describe the
traced seconds; ``drivers/serve_window_moe.TracedCapture``), and the decode
program's whole executions inside them (``harness/scoped_trace.py``).
Every function returns None where the program has no such counter or scope
(a parent commit), the configuration is another's, or the run traced
nothing."""

from harness import laguna_work as work
from harness.axk1_readers import traced_decode


def is_ours(cfg) -> bool:
    return bool(cfg) and cfg.get("model_type") == "laguna"


def decode_means(obs):
    reg, cfg = obs.get("traced_registry"), obs.get("config")
    if reg is None or not is_ours(cfg):
        return None
    steps = reg.value("znicz_serve_decode_steps_total")
    rows = {
        kind: reg.value("znicz_serve_decode_cached_rows_total", kind=kind)
        for kind in work.KINDS
    }
    pairs = reg.value("znicz_serve_moe_pairs_total", phase="decode")
    idle = reg.value("znicz_serve_moe_idle_experts_total", phase="decode")
    layer_steps = reg.value("znicz_serve_moe_layer_steps_total", phase="decode")
    if (
        not steps or not layer_steps or pairs is None or idle is None
        or None in rows.values()
    ):
        return None
    return {
        "cfg": cfg,
        # cached rows ONE layer of the kind read in a step
        "rows_read_per_step": {k: v / steps for k, v in rows.items()},
        "experts_hit_per_layer": cfg["num_experts"] - idle / layer_steps,
        "pairs_per_layer": pairs / layer_steps,
    }


def scope_roofline_pct(obs, scope, work_of):
    """100 x (least seconds of ``work_of(means)`` = (a layer's work in a
    step, layers)) x steps / the scope's device seconds, over the whole
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
            work.gqa_attention(cfg, kind, means["rows_read_per_step"][kind]),
            work.layers_of(cfg)[kind],
        )

    return scope_roofline_pct(obs, "attn_" + kind, work_of)
