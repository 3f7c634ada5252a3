"""Driver ``serve_latent_moe``: a latent-attention, routed-experts tower
(``znicz_tpu.workflow.latent_lm.LatentMoEModel``, configuration
``axk1-ep16``) behind the same ``PagedDecodeEngine`` + ``ServingFrontDoor``
+ HTTP server as ``serve_open_loop`` serves the classic LM through, under
the same open-loop traffic.

``serve_open_loop`` builds its weights, parameter tree, engine and
reference by name, so it cannot build this configuration; everything that
does not depend on the model is imported from it (the window, its
summary, the sample the reference reads, the warm server's HTTP side).
Here are: the server for this tower, its warm-up, and ``correct`` against
``reference/axk1.py``.

The traffic parameters are those of ``serve_open_loop``; the engine's
sizes are the configuration's ``serving`` group, ``n_blocks`` among them.
"""

from __future__ import annotations

import gc
import tempfile
import threading
import time

import numpy as np

from harness import axk1_weights, http_load, scoped_trace, traffic as traffic_gen
from harness.checks import Checks
from harness.loading import load_module

_open_loop = load_module("drivers", "serve_open_loop")
measure, summarise = _open_loop.measure, _open_loop.summarise
sample_for_check, p95 = _open_loop.sample_for_check, _open_loop.p95
OWN, WARM_NEW_TOKENS = _open_loop.OWN, _open_loop.WARM_NEW_TOKENS

# the scopes the program marks its new parts with (jax.named_scope)
SCOPES = ("mla_absorbed", "mla_materialised", "moe_dispatch", "moe_experts")


def model_of(cfg: dict):
    """The tower's kind as the engine takes it, from the configuration
    file: the published keys plus which experts live here."""
    from znicz_tpu.workflow.latent_lm import LatentMoEModel

    return LatentMoEModel.from_config(
        cfg, first_expert=cfg["deployment"]["first_expert"],
        max_positions=cfg["max_position_embeddings"],
    )


class Server(_open_loop.Server):
    """Weights, engine, front door and HTTP listener for one seed."""

    def __init__(self, cfg: dict, seed: int, deadline_s: float):
        from znicz_tpu.services import serve as serve_mod
        from znicz_tpu.services.engine import PagedDecodeEngine
        from znicz_tpu.services.frontdoor import ServingFrontDoor

        self._serve_mod = serve_mod
        self.cfg, serving = cfg, cfg["serving"]
        # first, before 8 GB of weights are drawn: a program without this
        # tower (a parent commit) ends the run here, at once
        model = model_of(cfg)
        self.weights = axk1_weights.weights(cfg, seed)
        params = axk1_weights.program_tree(self.weights)

        def factory():
            return PagedDecodeEngine(
                params, n_heads=cfg["num_attention_heads"],
                eos_id=cfg["assumed"]["eos_id"], batch_size=serving["slots"],
                admit_every=serving["admit_every"], max_seq=serving["max_seq"],
                block_size=serving["block_size"], n_blocks=serving["n_blocks"],
                model=model,
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

    def warm(self, rng, min_prompt: int) -> None:
        """One request per decode-window rung the traffic can reach (the
        engine's ladder: powers of two, the widest cut to the table's
        width), one at a time: with the prefill chunk that is every
        program the window runs."""
        serving = self.cfg["serving"]
        block, rows = serving["block_size"], serving["max_seq"] // serving["block_size"]
        rung = 1
        while rung < min_prompt // block + 1:
            rung *= 2
        rungs = []
        while True:
            rungs.append(min(rung, rows))
            if rung >= rows:
                break
            rung *= 2
        for blocks in rungs:
            length = min(blocks, rows - 1) * block - block // 2
            planned = traffic_gen.Planned(
                0.0, rng.integers(1, self.cfg["vocab_size"], length).tolist(),
                WARM_NEW_TOKENS, False,
            )
            done = http_load.run_open_loop(
                self.port, [planned], time.perf_counter(), 300.0, 300.0
            )[0]
            if done.error or done.done is None:
                raise RuntimeError(f"warm-up request failed: {done.error}")

    def prime_prefix(self, mix: dict, seed: int) -> None:
        """Put the traffic's shared prefix into the prefix cache before
        the ramp: one request that carries it, retired (the engine
        publishes a row's full blocks when it retires).  An application
        whose every request opens with the same handbook has it cached at
        any moment of a steady day; without this the ramp's first dozen
        requests each prefill all of it, side by side, and the window
        opens on their backlog."""
        shared = int(mix.get("shared_prefix_tokens", 0))
        if not shared:
            return
        gen_mix = {k: v for k, v in mix.items() if k not in OWN}
        prompt = traffic_gen.schedule(
            gen_mix, seed, 1.0, self.cfg["vocab_size"],
            self.cfg["serving"]["max_seq"],
            pad_to=self.cfg["serving"]["block_size"],
        )[0].prompt
        planned = traffic_gen.Planned(0.0, prompt, WARM_NEW_TOKENS, False)
        done = http_load.run_open_loop(
            self.port, [planned], time.perf_counter(), 300.0, 300.0
        )[0]
        if done.error or done.done is None:
            raise RuntimeError(f"priming request failed: {done.error}")

    def release(self) -> None:
        """Drop the engine and its pool (after ``close``), so that the
        reference has the chip's memory beside the weights."""
        self.door = self.http = None
        gc.collect()


def gaps_of(cfg, w, outcome, pad_to: int, *, control=None,
            cache_control=None) -> np.ndarray:
    """How far below the reference's best logit each served token lies
    (prompt, then the served tokens, once through the plain reference).
    With a control the tokens judged are not the served ones but those the
    reference itself puts first when it rounds every product's inputs
    (``control``) or the cached rows (``cache_control``) so."""
    import jax.numpy as jnp

    ref = load_module("reference", "axk1")
    prompt, served = outcome.planned.prompt, outcome.tokens
    sequence = list(prompt) + list(served[:-1])
    ref_logits = ref.logits(cfg, w, sequence, pad_to=pad_to)
    judged = served
    if control is not None or cache_control is not None:
        low = ref.logits(
            cfg, w, sequence, pad_to=pad_to,
            **({"cast": control} if control is not None else {}),
            **({"cache_cast": cache_control} if cache_control is not None else {}),
        )
        judged = jnp.argmax(low[len(prompt) - 1:], axis=-1)
    return np.asarray(ref.served_gaps(ref_logits, len(prompt), judged))


def decide_correct(cfg, w, good, seed: int, mix: dict, *, control=None,
                   cache_control=None):
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
    gaps = np.concatenate([
        gaps_of(cfg, w, o, pad_to, control=control, cache_control=cache_control)
        for o in sample
    ])
    print(
        f"reference read {len(sample)} requests, {gaps.size} served tokens",
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
        raise ValueError(f"serve_latent_moe does not know {sorted(unknown)}")
    t0 = time.perf_counter()
    server = Server(cfg, run_ctx.seed, float(mix["deadline_s"]))
    t1 = time.perf_counter()
    try:
        server.warm(
            np.random.default_rng(run_ctx.seed + 2),
            int(mix["prompt_tokens"].get("min", mix["prompt_tokens"].get("value", 1))),
        )
        server.prime_prefix(mix, run_ctx.seed)
        print(
            f"set-up inside the driver: weights, engine and server "
            f"{t1 - t0:.2f} s, warm-up and priming requests "
            f"{time.perf_counter() - t1:.2f} s, then the ramp", flush=True,
        )
        # the ramp is the last of set-up: the window opens when it ends
        run_ctx.mark_setup_done(extra_s=float(mix.get("ramp_s", 0.0)) + 0.05)
        capture = (
            scoped_trace.ScopedCapture(SCOPES, keep_dir=run_ctx.keep_trace_dir)
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
        "trace": capture.reduced if capture else None,
        "scoped": capture.scoped if capture else None,
        "config": cfg,
    }
    checks = decide_correct(cfg, server.weights, summary["good"], run_ctx.seed, mix)
    return {
        "metrics": summary["metrics"], "attempted": summary["attempted"],
        "failed": summary["failed"], "checks": checks,
        "observations": observations, "memory_peak_bytes": memory_peak,
    }
