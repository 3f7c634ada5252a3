"""Driver ``serve_sparse_gqa``: a tower whose every layer runs grouped-query
attention over the keys a learned indexer keeps, with softmax-routed
SiLU-gated experts all held (``znicz_tpu.workflow.sparse_gqa_lm
.SparseGQAMoEModel``, configuration ``keye-vl2-30b-a3b-l6``) behind the same
``PagedDecodeEngine`` + ``ServingFrontDoor`` + HTTP server as the other
serving cells, under the same open-loop traffic, with the prefix cache ON:
the traffic's shared prefix is primed in set-up and every request of the
ramp and the window maps its blocks, which carry the indexer's keys beside
K/V.

Everything that does not depend on the model comes from
``serve_open_loop`` (the window, its summary, the sample the reference
reads, the HTTP side), ``serve_latent_moe`` (the release of the pools
before the reference) and ``serve_window_moe`` (the capture that keeps the
traced seconds' share of the registry).  Here are: the server for this
tower, its priming, and ``correct`` against ``reference/keye.py``, which
computes the shared prefix's keys once a seed and each sampled request's
own tail against them.

The traffic parameters are those of ``serve_open_loop``; the engine's
sizes are the configuration's ``serving`` group.
"""

from __future__ import annotations

import json
import tempfile
import threading
import time

import numpy as np

from harness import http_load, keye_weights, traffic as traffic_gen
from harness.checks import Checks, float8
from harness.loading import load_module

_latent = load_module("drivers", "serve_latent_moe")
measure, summarise = _latent.measure, _latent.summarise
sample_for_check, OWN = _latent.sample_for_check, _latent.OWN
TracedCapture = load_module("drivers", "serve_window_moe").TracedCapture

# the scopes the program marks its parts with (jax.named_scope)
SCOPES = ("dsa_indexer", "dsa_select", "gqa_sparse", "moe_dispatch", "moe_experts")

# what a run's ``correct`` must catch, as arguments of the reference: the
# tokens judged are then those the reference itself puts first under them.
# ``prefix`` says whether the shared prefix's state is computed under the
# control too (the last one reads an honest state's indexer keys as zeros:
# a prefix cache that shares K/V only)
CONTROLS = {
    "float8_products": {"cast": float8},
    "no_selection": {"select": "all"},
    "selection_by_recency": {"select": "recent"},
    "index_topk_halved": {"index_topk_share": 0.5},
    "prefix_index_keys_unshared": {"past_index": "zero"},
}
PRIME_TIMEOUT_S = 1200.0


def model_of(cfg: dict):
    """The tower's kind as the engine takes it, from the configuration
    file's published keys."""
    from znicz_tpu.workflow.sparse_gqa_lm import SparseGQAMoEModel

    return SparseGQAMoEModel.from_config(cfg, max_positions=cfg["serving"]["max_seq"])


class Server(_latent.Server):
    """Weights, engine, front door and HTTP listener for one seed."""

    def __init__(self, cfg: dict, seed: int, deadline_s: float):
        from znicz_tpu.services import serve as serve_mod
        from znicz_tpu.services.engine import PagedDecodeEngine
        from znicz_tpu.services.frontdoor import ServingFrontDoor

        self._serve_mod = serve_mod
        self.cfg, serving = cfg, cfg["serving"]
        # first, before 8.75 GB of weights are drawn: a program without
        # this tower (a parent commit) ends the run here, at once
        model = model_of(cfg)
        self.weights = keye_weights.weights(cfg, seed)
        params = keye_weights.program_tree(self.weights)

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

    def prime(self, mix: dict, seed: int) -> None:
        """The traffic's shared prefix into the prefix cache, then one
        request that maps it: the first carries the prefix through every
        program the window runs (the prefill chunk; the decode chunk at the
        one rung prompts of this length reach) and publishes its blocks
        when it retires; the second is served as every request of the
        window is, from the cached chain on."""
        gen_mix = {k: v for k, v in mix.items() if k not in OWN}
        plan = traffic_gen.schedule(
            gen_mix, seed, 2.0 / float(mix["arrivals"]["rate_per_s"]),
            self.cfg["vocab_size"], self.cfg["serving"]["max_seq"],
            pad_to=self.cfg["serving"]["block_size"],
        )
        shared = int(mix["shared_prefix_tokens"])
        # own turns of the traffic's lengths but of other tokens than any
        # request of the ramp or the window carries
        rng = np.random.default_rng(seed + 2)
        for request in plan[:2]:
            own = rng.integers(
                1, self.cfg["vocab_size"], len(request.prompt) - shared
            ).tolist()
            planned = traffic_gen.Planned(
                0.0, request.prompt[:shared] + own, _latent.WARM_NEW_TOKENS, False
            )
            done = http_load.run_open_loop(
                self.port, [planned], time.perf_counter(), PRIME_TIMEOUT_S,
                PRIME_TIMEOUT_S,
            )[0]
            if done.error or done.done is None:
                raise RuntimeError(f"priming request failed: {done.error}")


def reference_arguments(cfg, control):
    """``(arguments of the prefix's state, arguments of a tail)`` of the
    reference under ``control`` (one of ``CONTROLS``' values, or None)."""
    control = dict(control or {})
    if "index_topk_share" in control:
        control["index_topk"] = int(
            cfg["sa_config"]["topk"] * control.pop("index_topk_share")
        )
    tail = dict(control)
    control.pop("past_index", None)
    return control, tail


class Reading:
    """What the reference says of one window's sample: the shared prefix's
    state computed ONCE (causality: its keys are the same for every
    request), then each side's gaps against it."""

    def __init__(self, cfg, w, sample, mix):
        self.ref = load_module("reference", "keye")
        self.cfg, self.w, self.sample = cfg, w, sample
        self.n_shared = int(mix["shared_prefix_tokens"])
        self.prefix = sample[0].planned.prompt[: self.n_shared]
        if any(o.planned.prompt[: self.n_shared] != self.prefix for o in sample):
            raise RuntimeError("the sampled requests do not share the prefix")
        self.sizes = dict(
            pad_to=max(len(o.planned.prompt) + len(o.tokens) for o in sample)
            - self.n_shared,
            rows_pad_to=max(len(o.tokens) for o in sample),
        )
        import jax

        t0 = time.perf_counter()
        self.state = jax.block_until_ready(self._state())
        self.state_s = time.perf_counter() - t0

    def _state(self, **arguments):
        if not self.n_shared:
            return None
        return self.ref.prefix_state(self.cfg, self.w, self.prefix, **arguments)

    def gaps(self, control=None):
        """``(gaps, shared)`` over the sample's served tokens: how far the
        reference's logit of each judged token lies under its best, and
        the share of the selected keys a bfloat16 indexer keeps too.
        Judged are the served tokens or, under ``control``, those the
        reference itself puts first under it."""
        import jax.numpy as jnp

        ref, cfg, w = self.ref, self.cfg, self.w
        of_state, of_tail = reference_arguments(cfg, control)
        low_state = self._state(**of_state) if of_state else self.state
        gaps, shared = [], []
        for o in self.sample:
            prompt, served = o.planned.prompt, o.tokens
            tail = list(prompt[self.n_shared:]) + list(served[:-1])
            first_row = len(prompt) - self.n_shared - 1
            ref_logits, share = ref.forward(
                cfg, w, tail, state=self.state, first_row=first_row, **self.sizes
            )
            judged = served
            if control is not None:
                judged = jnp.argmax(
                    ref.logits(
                        cfg, w, tail, state=low_state, first_row=first_row,
                        **self.sizes, **of_tail,
                    ),
                    axis=-1,
                )
            found = np.asarray(ref.served_gaps(ref_logits, judged))
            gaps.append(found)
            shared.append(np.asarray(share)[: len(found)])
        return np.concatenate(gaps), np.concatenate(shared)


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
    reading = Reading(cfg, w, sample, mix)
    t1 = time.perf_counter()
    gaps, shared = reading.gaps(control)
    print(
        f"reference read the shared prefix's {reading.n_shared} tokens in "
        f"{reading.state_s:.1f} s and {len(sample)} requests, {gaps.size} served "
        f"tokens, the longest tail {reading.sizes['pad_to']} tokens, in "
        f"{time.perf_counter() - t1:.1f} s",
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
        raise ValueError(f"serve_sparse_gqa does not know {sorted(unknown)}")
    t0 = time.perf_counter()
    server = Server(cfg, run_ctx.seed, float(mix["deadline_s"]))
    t1 = time.perf_counter()
    try:
        server.prime(mix, run_ctx.seed)
        print(
            f"set-up inside the driver: weights, engine and server "
            f"{t1 - t0:.2f} s, priming requests {time.perf_counter() - t1:.2f} s, "
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
