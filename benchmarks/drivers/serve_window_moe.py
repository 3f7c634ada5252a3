"""Driver ``serve_window_moe``: a tower of window layers with rotary
positions beside global layers without, grouped-query attention and
ReLU-gated routed experts (``znicz_tpu.workflow.window_lm
.WindowGQAMoEModel``, configuration ``smallthinker-21b-l8``) behind the
same ``PagedDecodeEngine`` + ``ServingFrontDoor`` + HTTP server as the
other serving cells, under the same open-loop traffic.

Everything that does not depend on the model comes from
``serve_open_loop`` (the window, its summary, the sample the reference
reads, the HTTP side) and, for a tower handed to the engine as ``model=``,
from ``serve_latent_moe`` (the warm-up over the decode rungs, the
release of the pools before the reference).  Here are: the server for this
tower, and ``correct`` against ``reference/smallthinker.py``.

The traffic parameters are those of ``serve_open_loop``; the engine's
sizes are the configuration's ``serving`` group, ``n_blocks`` by kind of
block among them.
"""

from __future__ import annotations

import json
import tempfile
import threading
import time

import numpy as np

from harness import (
    registry, scoped_trace, smallthinker_weights, traffic as traffic_gen,
)
from harness.checks import Checks
from harness.loading import load_module

_latent = load_module("drivers", "serve_latent_moe")
measure, summarise = _latent.measure, _latent.summarise
sample_for_check, OWN = _latent.sample_for_check, _latent.OWN

# the scopes the program marks its new parts with (jax.named_scope)
SCOPES = ("attn_window", "attn_global", "moe_dispatch", "moe_experts")


class TracedCapture(scoped_trace.ScopedCapture):
    """A ``ScopedCapture`` that also keeps the registry's share of the
    seconds it traced (``traced_registry``).  What a decode step reads
    follows the rows resident, which swing between a few hundred and 14k
    tokens in this traffic: the window's mean of a counter beside three
    seconds' device time read rooflines of 102 and 111 % (my chip run, PR
    31), so the roofline readers take both from the same seconds."""

    def __init__(self, markers, keep_dir=None):
        super().__init__(markers, keep_dir=keep_dir)
        self._before = self._after = None

    def start(self) -> None:
        super().start()
        self._before = registry.read()

    def stop(self) -> None:
        self._after = registry.read()
        super().stop()

    @property
    def traced_registry(self):
        if self._before is None or self._after is None:
            return None
        return registry.Delta(self._before, self._after)


def model_of(cfg: dict):
    """The tower's kind as the engine takes it, from the configuration
    file's published keys."""
    from znicz_tpu.workflow.window_lm import WindowGQAMoEModel

    return WindowGQAMoEModel.from_config(
        cfg, max_positions=cfg["max_position_embeddings"]
    )


class Server(_latent.Server):
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
        self.weights = smallthinker_weights.weights(cfg, seed)
        params = smallthinker_weights.program_tree(self.weights)

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


def gaps_of(cfg, w, outcome, pad_to: int, rows_pad_to: int, *, control=None,
            cache_control=None) -> np.ndarray:
    """How far below the reference's best logit each served token lies
    (prompt, then the served tokens, once through the plain reference; the
    head over the served positions alone).  With a control the tokens
    judged are not the served ones but those the reference itself puts
    first when it rounds every product's inputs (``control``) or the
    cached K and V (``cache_control``) so."""
    import jax.numpy as jnp

    ref = load_module("reference", "smallthinker")
    prompt, served = outcome.planned.prompt, outcome.tokens
    sequence = list(prompt) + list(served[:-1])
    sizes = dict(
        pad_to=pad_to, first_row=len(prompt) - 1, rows_pad_to=rows_pad_to
    )
    ref_logits = ref.logits(cfg, w, sequence, **sizes)
    judged = served
    if control is not None or cache_control is not None:
        low = ref.logits(
            cfg, w, sequence, **sizes,
            **({"cast": control} if control is not None else {}),
            **({"cache_cast": cache_control} if cache_control is not None else {}),
        )
        judged = jnp.argmax(low, axis=-1)
    return np.asarray(ref.served_gaps(ref_logits, judged))


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
    rows_pad_to = max(len(o.tokens) for o in sample)
    gaps = np.concatenate([
        gaps_of(cfg, w, o, pad_to, rows_pad_to, control=control,
                cache_control=cache_control)
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
        raise ValueError(f"serve_window_moe does not know {sorted(unknown)}")
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
        "trace": capture.reduced if capture else None,
        "scoped": capture.scoped if capture else None,
        "traced_registry": capture.traced_registry if capture else None,
        "config": cfg,
    }
    if capture and capture.scoped:
        # what PERF.md's "where a step's time goes" is written from
        print(f"device seconds by program and scope: {json.dumps(capture.scoped)}",
              flush=True)
    checks = decide_correct(cfg, server.weights, summary["good"], run_ctx.seed, mix)
    return {
        "metrics": summary["metrics"], "attempted": summary["attempted"],
        "failed": summary["failed"], "checks": checks,
        "observations": observations, "memory_peak_bytes": memory_peak,
    }
