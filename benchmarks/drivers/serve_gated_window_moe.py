"""Driver ``serve_gated_window_moe``: a tower of window and global
grouped-query layers that differ in query heads, each with a rotary of its
own and a gate a head, over a dense first layer and sigmoid-routed experts
beside a shared one (``znicz_tpu.workflow.gated_window_lm
.GatedWindowGQAMoEModel``, configuration ``laguna-xs2-stage1``) behind the
same ``PagedDecodeEngine`` + ``ServingFrontDoor`` + HTTP server as the other
serving cells, under the same open-loop traffic, with the prefix cache asked
for BY NAME (a tower with a window kind of cache blocks is served without
one by default): the traffic's shared prefix is primed in set-up, and every
request of the ramp and the window maps its global blocks and the window
kind's last four.

Everything that does not depend on the model comes from
``serve_open_loop`` (the window, its summary, the sample the reference
reads, the HTTP side), ``serve_latent_moe`` (the release of the pools
before the reference) and ``serve_window_moe`` (the capture that keeps the
traced seconds' share of the registry); the shape of ``prime`` and
``Reading`` is ``serve_sparse_gqa``'s.  Here are: the server for this
tower, its priming, and ``correct`` against ``reference/laguna.py``, which
computes the shared prefix's keys and values once a seed and each sampled
request's own tail against them.

The traffic parameters are those of ``serve_open_loop``; the engine's
sizes are the configuration's ``serving`` group.
"""

from __future__ import annotations

import json
import tempfile
import threading
import time

import numpy as np

from harness import http_load, laguna_weights, traffic as traffic_gen
from harness.checks import Checks, float8
from harness.loading import load_module

_latent = load_module("drivers", "serve_latent_moe")
measure, summarise = _latent.measure, _latent.summarise
sample_for_check, OWN = _latent.sample_for_check, _latent.OWN
TracedCapture = load_module("drivers", "serve_window_moe").TracedCapture

# the scopes the program marks its parts with (jax.named_scope)
SCOPES = (
    "attn_window", "attn_global", "attn_gate", "moe_dispatch", "moe_experts",
    "moe_shared", "ffn_dense",
)

# what a run's ``correct`` must catch, as arguments of the reference (the
# shared prefix's state is computed under them too): the tokens judged are
# then those the reference itself puts first under them
CONTROLS = {
    "float8_products": {"cast": float8},
    "float8_cache": {"cache_cast": float8},
    "no_gate": {"gate": False},
    "window_1024": {"window": 1024},
    "plain_rotary_on_full_layers": {"full_rope": "plain"},
}
PRIME_TIMEOUT_S = 600.0


def model_of(cfg: dict):
    """The tower's kind as the engine takes it, from the configuration
    file's published keys."""
    from znicz_tpu.workflow.gated_window_lm import GatedWindowGQAMoEModel

    return GatedWindowGQAMoEModel.from_config(
        cfg, max_positions=cfg["serving"]["max_seq"]
    )


class Server(_latent.Server):
    """Weights, engine, front door and HTTP listener for one seed."""

    def __init__(self, cfg: dict, seed: int, deadline_s: float):
        from znicz_tpu.services import serve as serve_mod
        from znicz_tpu.services.engine import PagedDecodeEngine
        from znicz_tpu.services.frontdoor import ServingFrontDoor

        self._serve_mod = serve_mod
        self.cfg, serving = cfg, cfg["serving"]
        # first, before 11 GB of weights are drawn: a program without this
        # tower (a parent commit) ends the run here, at once
        model = model_of(cfg)
        self.weights = laguna_weights.weights(cfg, seed)
        params = laguna_weights.program_tree(self.weights)

        def factory():
            return PagedDecodeEngine(
                params, n_heads=cfg["num_attention_heads"],
                eos_id=cfg["assumed"]["eos_id"], batch_size=serving["slots"],
                admit_every=serving["admit_every"], max_seq=serving["max_seq"],
                block_size=serving["block_size"], n_blocks=serving["n_blocks"],
                prefill_budget=serving.get("prefill_budget"),
                prefix_cache=serving["prefix_cache"], model=model,
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
        request for each decode rung the traffic reaches.  The first call IS
        the prefix, so that its row retires at the prefix's last block
        boundary holding the window kind's blocks of the last window (a
        request that ran on would hold, and publish, the window of its own
        end).  The others open with the prefix and map it as every request
        of the window does: own turns of other tokens than any request of
        the ramp or the window carries, one that stays under the decode
        program's narrower rung and one past it."""
        serving = self.cfg["serving"]
        block, rows = serving["block_size"], serving["max_seq"] // serving["block_size"]
        gen_mix = {k: v for k, v in mix.items() if k not in OWN}
        prompt = traffic_gen.schedule(
            gen_mix, seed, 1.0 / float(mix["arrivals"]["rate_per_s"]),
            self.cfg["vocab_size"], serving["max_seq"], pad_to=block,
        )[0].prompt
        shared = int(mix["shared_prefix_tokens"])
        rung = 1
        while rung * block < int(mix["prompt_tokens"]["min"]):
            rung *= 2
        lengths = [int(mix["prompt_tokens"]["min"])]
        if rung < rows:  # the widest rung is cut to the table's width
            lengths.append(min(rung * block + block // 2, int(mix["prompt_tokens"]["max"])))
        rng = np.random.default_rng(seed + 2)
        plans = [traffic_gen.Planned(0.0, prompt[:shared], 1, False)] + [
            traffic_gen.Planned(
                0.0,
                prompt[:shared] + rng.integers(
                    1, self.cfg["vocab_size"], length - shared
                ).tolist(),
                _latent.WARM_NEW_TOKENS, False,
            )
            for length in lengths
        ]
        for planned in plans:
            done = http_load.run_open_loop(
                self.port, [planned], time.perf_counter(), PRIME_TIMEOUT_S,
                PRIME_TIMEOUT_S,
            )[0]
            if done.error or done.done is None:
                raise RuntimeError(f"priming request failed: {done.error}")


class Reading:
    """What the reference says of one window's sample: the shared prefix's
    keys and values computed ONCE a side (causality: they are the same for
    every request), then each request's own tail against them."""

    def __init__(self, cfg, w, sample, mix):
        self.ref = load_module("reference", "laguna")
        self.cfg, self.w, self.sample = cfg, w, sample
        self.n_shared = int(mix["shared_prefix_tokens"])
        self.prefix = sample[0].planned.prompt[: self.n_shared]
        if any(o.planned.prompt[: self.n_shared] != self.prefix for o in sample):
            raise RuntimeError("the sampled requests do not share the prefix")
        self.sizes = dict(
            n_past=self.n_shared,
            pad_to=max(len(o.planned.prompt) + len(o.tokens) for o in sample)
            - self.n_shared,
            rows_pad_to=max(len(o.tokens) for o in sample),
        )
        import jax

        t0 = time.perf_counter()
        self.state = jax.block_until_ready(self._state())
        self.state_s = time.perf_counter() - t0

    def _state(self, **control):
        if not self.n_shared:
            return None
        return self.ref.prefix_state(self.cfg, self.w, self.prefix, **control)

    def gaps(self, control=None):
        """How far the reference's logit of each judged token lies under
        its best, over the sample's served tokens.  Judged are the served
        tokens or, under ``control``, those the reference itself puts first
        under it."""
        import jax.numpy as jnp

        low_state = self._state(**control) if control else None
        gaps = []
        for o in self.sample:
            prompt, served = o.planned.prompt, o.tokens
            tail = list(prompt[self.n_shared:]) + list(served[:-1])
            first_row = len(prompt) - self.n_shared - 1
            ref_logits = self.ref.logits(
                self.cfg, self.w, tail, state=self.state, first_row=first_row,
                **self.sizes,
            )
            judged = served
            if control:
                judged = jnp.argmax(
                    self.ref.logits(
                        self.cfg, self.w, tail, state=low_state,
                        first_row=first_row, **self.sizes, **control,
                    ),
                    axis=-1,
                )
            gaps.append(np.asarray(self.ref.served_gaps(ref_logits, judged)))
        return np.concatenate(gaps)


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
    gaps = reading.gaps(control)
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
    return checks


def run(run_ctx) -> dict:
    cfg, mix = run_ctx.config, run_ctx.traffic
    unknown = set(mix) - OWN - traffic_gen.KNOWN
    if unknown:
        raise ValueError(f"serve_gated_window_moe does not know {sorted(unknown)}")
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
