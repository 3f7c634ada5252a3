"""What the per-layer readers of ``dots3-ep16-l5`` share: the decode
counters of the TRACED seconds as per-step means (``traced_registry``, the
registry's delta between the trace's start and stop: what a step reads
swings with the rows resident, so the window's mean does not describe the
traced seconds; ``drivers/serve_window_moe.TracedCapture``), and the
programs' whole executions inside them (``harness/scoped_trace.py``).
Every function returns None where the program has no such counter or
scope (a parent commit) or the run traced nothing."""

from harness import dots3_work as work
from harness.axk1_readers import traced_decode


def decode_means(obs):
    reg, cfg = obs.get("traced_registry"), obs.get("config")
    if reg is None or not cfg or "index_topk" not in cfg:
        return None
    steps = reg.value("znicz_serve_decode_steps_total")
    read = {
        "scored": reg.value("znicz_serve_sparse_keys_scored_total", phase="decode"),
        "selected": reg.value("znicz_serve_sparse_keys_selected_total", phase="decode"),
        "window": reg.value("znicz_serve_decode_cached_rows_total", kind="window"),
    }
    if not steps or None in read.values():
        return None
    # what ONE layer of the kind read in a step
    return {"cfg": cfg, **{k: v / steps for k, v in read.items()}}


def scope_roofline_pct(obs, scope, work_of):
    """100 x (least seconds of ``work_of(means)``, a layer and a step) x
    layers x steps / the scope's device seconds, over the whole executions
    of the decode program in the trace."""
    means, traced = decode_means(obs), traced_decode(obs)
    if means is None or traced is None or not obs.get("peaks"):
        return None
    seconds = traced["scopes"].get(scope)
    if not seconds:
        return None
    job, layers = work_of(means)
    least = work.least_seconds(job, obs["peaks"]) * layers * traced["steps"]
    return 100.0 * least / seconds


def prefill_scopes_ms_per_chunk(obs, scopes):
    """Device milliseconds of ``scopes`` in one execution of the prefill
    program (one chunk), over its whole executions in the trace."""
    entry = (obs.get("scoped") or {}).get(obs.get("prefill_program"))
    if not entry or not entry["whole_executions"]:
        return None
    found = [entry["scopes"].get(s) for s in scopes]
    if not any(found):
        return None
    return 1e3 * sum(s or 0.0 for s in found) / entry["whole_executions"]
