"""Driver ``serve_open_loop``: the LM behind ``PagedDecodeEngine`` +
``ServingFrontDoor`` + the HTTP server, under open-loop traffic.

Real ``POST /generate`` streams over loopback against one front door, in
this one process (server threads, engine thread and the load generator's
single thread): requests are sent when they are due, at the rate fixed in
the cell, and every streamed token is stamped by the client's clock.

The traffic parameters are the general generator's (``harness/traffic.py``)
plus, for this driver: ``check_requests`` (how many finished requests the
reference reads, the longest always among them), ``trace_s`` (seconds of
device trace in a ``--trace 1`` run) and ``limits``.  The engine's sizes
(``slots``, ``max_seq``, ``block_size``, ``admit_every``) are the
configuration's ``serving`` group.
"""

from __future__ import annotations

import statistics
import tempfile
import threading
import time

import numpy as np

from harness import http_load, registry, traffic as traffic_gen, weights
from harness.checks import Checks
from harness.loading import load_module

OWN = {"check_requests", "trace_s", "limits"}
WARM_NEW_TOKENS = 8


def program_tree(w: dict) -> list:
    """The benchmark's named weights in the list the program's LM takes:
    ``[embed, block_0 .. block_L-1, head]`` (the arrays are shared, not
    copied)."""
    return (
        [{"embed": w["embed"], "pos": w["pos"]}]
        + [dict(b) for b in w["blocks"]]
        + [{"head": w["head"]}]
    )


def p95(values) -> float:
    """95th percentile of all the values, by linear interpolation."""
    return float(np.percentile(np.asarray(values, np.float64), 95))


class Server:
    """Weights, engine, front door and HTTP listener for one seed."""

    def __init__(self, cfg: dict, seed: int, deadline_s: float):
        from znicz_tpu.services import serve as serve_mod
        from znicz_tpu.services.engine import PagedDecodeEngine
        from znicz_tpu.services.frontdoor import ServingFrontDoor

        self._serve_mod = serve_mod
        self.cfg, serving = cfg, cfg["serving"]
        self.weights = weights.lm_weights(cfg, seed)
        params = program_tree(self.weights)

        def factory():
            return PagedDecodeEngine(
                params, n_heads=cfg["n_head"], eos_id=cfg["assumed"]["eos_id"],
                batch_size=serving["slots"], admit_every=serving["admit_every"],
                max_seq=serving["max_seq"], block_size=serving["block_size"],
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
        """One request per decode-window rung the traffic can reach, one
        at a time: with the prefill chunk that is every program the window
        runs."""
        serving = self.cfg["serving"]
        block, rows = serving["block_size"], serving["max_seq"] // serving["block_size"]
        rung = 1
        while rung < min_prompt // block + 1:
            rung *= 2
        plan = []
        while rung <= rows:
            length = min(rung, rows - 1) * block - block // 2
            plan.append(
                traffic_gen.Planned(
                    0.0, rng.integers(1, self.cfg["vocab_size"], length).tolist(),
                    WARM_NEW_TOKENS, False,
                )
            )
            rung *= 2
        for planned in plan:
            done = http_load.run_open_loop(
                self.port, [planned], time.perf_counter(), 180.0, 180.0
            )[0]
            if done.error or done.done is None:
                raise RuntimeError(f"warm-up request failed: {done.error}")

    def close(self) -> None:
        self._serve_mod.shutdown_gracefully(self.http, self.door, grace_s=5.0)
        self.http.server_close()
        self._thread.join(timeout=10)
        self._dir.cleanup()


def measure(server: Server, mix: dict, seed: int, seconds: float,
            capture=None, trace_s: float = 0.0) -> dict:
    """Ramp, window and drain against a warm server.  Returns the
    outcomes of the window's requests and the window's registry share."""
    cfg = server.cfg
    gen_mix = {k: v for k, v in mix.items() if k not in OWN}
    plan = traffic_gen.schedule(
        gen_mix, seed, seconds, cfg["vocab_size"], cfg["serving"]["max_seq"],
        pad_to=cfg["serving"]["block_size"],
    )
    deadline_s = float(mix["deadline_s"])
    t_open = time.perf_counter() + float(mix.get("ramp_s", 0.0)) + 0.05
    marks = {"before": None, "after": None}

    def on_tick(now_s: float) -> None:
        if marks["before"] is None and now_s >= 0.0:
            marks["before"] = registry.read()
        if marks["after"] is None and now_s >= seconds:
            marks["after"] = registry.read()

    if capture is not None:
        capture.run_for(t_open - time.perf_counter() + seconds / 2, trace_s)
    outcomes = http_load.run_open_loop(
        server.port, plan, t_open, deadline_s, seconds + deadline_s, on_tick
    )
    if capture is not None:
        capture.join()
    after = marks["after"] or registry.read()
    return {
        "outcomes": [o for o in outcomes if o.planned.counted],
        "all_outcomes": outcomes,
        "delta": registry.Delta(marks["before"] or after, after),
    }


def summarise(measured: dict, seconds: float, deadline_s: float) -> dict:
    """The end-to-end numbers of one window, and the earlier line the
    issue asks for.  A request that failed, was refused or ran past its
    deadline counts as failed and is missing from every latency."""
    outcomes = measured["outcomes"]
    good, failed = [], 0
    for o in outcomes:
        ok = (
            o.error is None and o.done is not None and o.tokens
            and o.done.get("finish_reason") in ("eos", "budget")
            and o.end_s is not None
            and o.end_s - o.planned.due_s <= deadline_s
        )
        if ok:
            good.append(o)
        else:
            failed += 1
    ttft = [1e3 * (o.first_s - o.planned.due_s) for o in good]
    tpot = [
        1e3 * (o.last_s - o.first_s) / (len(o.tokens) - 1)
        for o in good if len(o.tokens) > 1
    ]
    in_window = sum(
        1 for o in measured["all_outcomes"] for t in o.arrivals
        if 0.0 <= t < seconds
    )
    late = [1e3 * (o.sent_s - o.planned.due_s) for o in outcomes if o.sent_s is not None]
    metrics, client = {}, {}
    if ttft and tpot:
        metrics = {
            "tpot_p95_ms": p95(tpot), "tokens_per_s": in_window / seconds,
        }
        # recorded, not judged: with some forty requests in a window its
        # run-to-run spread is wider than any bound may be (PERF.md)
        client = {"ttft_p95_ms": p95(ttft)}
        print(
            f"serve_open_loop: {len(outcomes)} requests due in the window, "
            f"{failed} failed; generator lateness ms median "
            f"{statistics.median(late):.3f} max {max(late):.3f}; medians: ttft "
            f"{statistics.median(ttft):.2f} ms, tpot {statistics.median(tpot):.3f} "
            f"ms; ttft p95 {client['ttft_p95_ms']:.1f} ms; {in_window} tokens "
            f"in the window",
            flush=True,
        )
    return {
        "metrics": metrics, "client": client, "attempted": len(outcomes),
        "failed": failed, "good": good,
    }


def gaps_of(cfg, w, outcome, *, control=None) -> np.ndarray:
    """How far below the reference's best logit each served token lies,
    position by position (prompt, then the served tokens, once through
    the plain reference).  With ``control`` (a rounding such as
    ``harness.checks.float8``) the tokens judged are not the served ones but those the
    reference puts first when it rounds every product's inputs so."""
    import jax.numpy as jnp

    ref = load_module("reference", "lm")
    prompt, served = outcome.planned.prompt, outcome.tokens
    sequence = list(prompt) + list(served[:-1])
    ref_logits = ref.logits(cfg, w, sequence)
    judged = served
    if control is not None:
        low = ref.logits(cfg, w, sequence, cast=control)
        judged = jnp.argmax(low[len(prompt) - 1:], axis=-1)
    return np.asarray(ref.served_gaps(ref_logits, len(prompt), judged))


def sample_for_check(good, seed: int, n: int):
    """A seeded sample of the finished requests, the longest in it."""
    if not good:
        return []
    longest = max(good, key=lambda o: len(o.planned.prompt) + len(o.tokens))
    rest = [o for o in good if o is not longest]
    rng = np.random.default_rng(int(seed) + 1)
    picks = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in picks]


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
    gaps = np.concatenate(
        [gaps_of(cfg, w, o, control=control) for o in sample]
    )
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
        raise ValueError(f"serve_open_loop does not know {sorted(unknown)}")
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
        capture = run_ctx.new_capture() if run_ctx.trace else None
        measured = measure(
            server, mix, run_ctx.seed, run_ctx.seconds, capture,
            float(mix.get("trace_s", 1.0)),
        )
        memory_peak = run_ctx.memory_peak_bytes()
    finally:
        server.close()
    summary = summarise(measured, run_ctx.seconds, float(mix["deadline_s"]))
    observations = {
        "registry": measured["delta"],
        "client": summary["client"],
        "decode_program": "jit__paged_decode_chunk",
        "trace": capture.reduced if capture else None,
    }
    checks = decide_correct(cfg, server.weights, summary["good"], run_ctx.seed, mix)
    return {
        "metrics": summary["metrics"], "attempted": summary["attempted"],
        "failed": summary["failed"], "checks": checks,
        "observations": observations, "memory_peak_bytes": memory_peak,
    }
