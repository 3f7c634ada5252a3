"""Driver ``serve_sparse_latent``: a tower whose full layers keep the keys
a learned indexer chooses and whose other layers run a second, wider
latent attention behind a short window, with sigmoid-routed experts chosen
with a score bias (``znicz_tpu.workflow.sparse_latent_lm
.SparseLatentMoEModel``, configuration ``dots3-ep16-l5``) behind the same
``PagedDecodeEngine`` + ``ServingFrontDoor`` + HTTP server as the other
serving cells, under the same open-loop traffic.

Everything that does not depend on the model comes from
``serve_open_loop`` (the window, its summary, the sample the reference
reads, the HTTP side), ``serve_latent_moe`` (the warm-up over the decode
rungs, the release of the pools before the reference) and
``serve_window_moe`` (the capture that keeps the traced seconds' share of
the registry).  Here are: the server for this tower, and ``correct``
against ``reference/dots3.py``.

The traffic parameters are those of ``serve_open_loop``; the engine's
sizes are the configuration's ``serving`` group, ``n_blocks`` by kind of
block and ``prefill_budget`` among them.
"""

from __future__ import annotations

import json
import tempfile
import threading
import time

import numpy as np

from harness import dots3_weights, traffic as traffic_gen
from harness.checks import Checks, float8
from harness.loading import load_module

_latent = load_module("drivers", "serve_latent_moe")
measure, summarise = _latent.measure, _latent.summarise
sample_for_check, OWN = _latent.sample_for_check, _latent.OWN
TracedCapture = load_module("drivers", "serve_window_moe").TracedCapture

# the scopes the program marks its new parts with (jax.named_scope)
SCOPES = (
    "dsa_indexer", "dsa_select", "mla_sparse", "mla_window", "moe_dispatch",
    "moe_experts",
)

# what a run's ``correct`` must catch, as arguments of the reference: the
# tokens judged are then those the reference itself puts first under them
CONTROLS = {
    "float8_products": {"cast": float8},
    "no_selection": {"select": "all"},
    "selection_by_recency": {"select": "recent"},
    "index_topk_halved": {"index_topk_share": 0.5},
}


def model_of(cfg: dict):
    """The tower's kind as the engine takes it, from the configuration
    file: the published keys plus which experts live here."""
    from znicz_tpu.workflow.sparse_latent_lm import SparseLatentMoEModel

    return SparseLatentMoEModel.from_config(
        cfg, first_expert=cfg["deployment"]["first_expert"],
        max_positions=cfg["max_position_embeddings"],
    )


class Server(_latent.Server):
    """Weights, engine, front door and HTTP listener for one seed."""

    def __init__(self, cfg: dict, seed: int, deadline_s: float):
        from znicz_tpu.services import serve as serve_mod
        from znicz_tpu.services.engine import PagedDecodeEngine
        from znicz_tpu.services.frontdoor import ServingFrontDoor

        self._serve_mod = serve_mod
        self.cfg, serving = cfg, cfg["serving"]
        # first, before 5 GB of weights are drawn: a program without this
        # tower (a parent commit) ends the run here, at once
        model = model_of(cfg)
        self.weights = dots3_weights.weights(cfg, seed)
        params = dots3_weights.program_tree(self.weights)

        def factory():
            return PagedDecodeEngine(
                params, n_heads=cfg["num_attention_heads"],
                eos_id=cfg["assumed"]["eos_id"], batch_size=serving["slots"],
                admit_every=serving["admit_every"], max_seq=serving["max_seq"],
                block_size=serving["block_size"], n_blocks=serving["n_blocks"],
                prefill_budget=serving.get("prefill_budget"), model=model,
            )

        self.door = ServingFrontDoor(
            factory, max_pending=1 << 16, default_deadline_s=deadline_s
        )
        self._dir = tempfile.TemporaryDirectory(prefix="znicz_bench_serve_")
        self.http = serve_mod.build_server(
            directory=self._dir.name, port=0, frontdoor=self.door
        )
        self.port = self.http.server_address[1]
        self._thread = threading.Thread(
            target=self.http.serve_forever, daemon=True
        )
        self._thread.start()


def read_request(cfg, w, outcome, pad_to: int, rows_pad_to: int, *,
                 control=None):
    """``(gaps, shared)`` of one served request: how far below the
    reference's best logit each served token lies (prompt, then the served
    tokens, once through the plain reference; the head over the served
    positions alone), and the share of each served row's selected keys that
    a bfloat16 indexer selects too.  With a ``control`` (one of
    ``CONTROLS``' values) the tokens judged are not the served ones but
    those the reference itself puts first under it."""
    import jax.numpy as jnp

    ref = load_module("reference", "dots3")
    prompt, served = outcome.planned.prompt, outcome.tokens
    sequence = list(prompt) + list(served[:-1])
    sizes = dict(
        pad_to=pad_to, first_row=len(prompt) - 1, rows_pad_to=rows_pad_to
    )
    ref_logits, shared = ref.forward(cfg, w, sequence, **sizes)
    judged = served
    if control is not None:
        control = dict(control)
        if "index_topk_share" in control:
            control["index_topk"] = int(
                cfg["index_topk"] * control.pop("index_topk_share")
            )
        judged = jnp.argmax(ref.logits(cfg, w, sequence, **sizes, **control), axis=-1)
    return np.asarray(ref.served_gaps(ref_logits, judged)), np.asarray(shared)


def decide_correct(cfg, w, good, seed: int, mix: dict, *, control=None):
    eos = cfg["assumed"]["eos_id"]
    sample = sample_for_check(good, seed, int(mix["check_requests"]))
    checks = Checks()
    if not sample:
        checks.at_most("finished_requests_missing", 1.0, 0.0)
        return checks
    short = sum(
        1 for o in sample
        if len(o.tokens) != o.planned.max_new_tokens and o.tokens[-1] != eos
    )
    pad_to = max(len(o.planned.prompt) + len(o.tokens) for o in sample)
    rows_pad_to = max(len(o.tokens) for o in sample)
    t0 = time.perf_counter()
    read = [
        read_request(cfg, w, o, pad_to, rows_pad_to, control=control)
        for o in sample
    ]
    gaps = np.concatenate([g for g, _ in read])
    shared = np.concatenate([s[: len(g)] for g, s in read])
    print(
        f"reference read {len(sample)} requests, {gaps.size} served tokens, "
        f"the longest {pad_to} tokens, in {time.perf_counter() - t0:.1f} s",
        flush=True,
    )
    limits = mix["limits"]
    checks.at_most("answers_cut_short", short, 0.0)
    checks.at_most("served_logit_gap_widest", gaps.max(), limits["served_logit_gap_widest"])
    checks.at_most("served_logit_gap_mean", gaps.mean(), limits["served_logit_gap_mean"])
    # beside the gaps, not instead of them: where two honest selections
    # differ at the 2,048th score's rounding the gaps' limits carry it
    checks.at_most(
        "selected_keys_not_shared_mean", 1.0 - shared.mean(),
        limits["selected_keys_not_shared_mean"],
    )
    return checks


def run(run_ctx) -> dict:
    cfg, mix = run_ctx.config, run_ctx.traffic
    unknown = set(mix) - OWN - traffic_gen.KNOWN
    if unknown:
        raise ValueError(f"serve_sparse_latent does not know {sorted(unknown)}")
    t0 = time.perf_counter()
    server = Server(cfg, run_ctx.seed, float(mix["deadline_s"]))
    t1 = time.perf_counter()
    try:
        server.warm(
            np.random.default_rng(run_ctx.seed + 2),
            int(mix["prompt_tokens"].get("min", mix["prompt_tokens"].get("value", 1))),
        )
        print(
            f"set-up inside the driver: weights, engine and server "
            f"{t1 - t0:.2f} s, warm-up requests {time.perf_counter() - t1:.2f} s, "
            f"then the ramp", flush=True,
        )
        # the ramp is the last of set-up: the window opens when it ends
        run_ctx.mark_setup_done(extra_s=float(mix.get("ramp_s", 0.0)) + 0.05)
        capture = (
            TracedCapture(SCOPES, keep_dir=run_ctx.keep_trace_dir)
            if run_ctx.trace else None
        )
        measured = measure(
            server, mix, run_ctx.seed, run_ctx.seconds, capture,
            float(mix.get("trace_s", 1.0)),
        )
        memory_peak = run_ctx.memory_peak_bytes()
    finally:
        server.close()
    server.release()
    summary = summarise(measured, run_ctx.seconds, float(mix["deadline_s"]))
    observations = {
        "registry": measured["delta"],
        "client": summary["client"],
        "decode_program": "jit__paged_decode_chunk",
        "prefill_program": "jit__paged_prefill_prog",
        "trace": capture.reduced if capture else None,
        "scoped": capture.scoped if capture else None,
        "traced_registry": capture.traced_registry if capture else None,
        "config": cfg,
    }
    if capture and capture.scoped:
        # what PERF.md's "where the time goes" is written from
        print(f"device seconds by program and scope: {json.dumps(capture.scoped)}",
              flush=True)
    checks = decide_correct(cfg, server.weights, summary["good"], run_ctx.seed, mix)
    return {
        "metrics": summary["metrics"], "attempted": summary["attempted"],
        "failed": summary["failed"], "checks": checks,
        "observations": observations, "memory_peak_bytes": memory_peak,
    }
