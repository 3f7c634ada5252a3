"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py              # one chip: device, native, train, zoo, serve
    python chip_smoke.py --multichip  # four chips: data-parallel AlexNet only

One process, phases in sequence, through the entry points a user would
call (``znicz_tpu.launcher.run_args`` — what ``python -m znicz_tpu`` runs —
and ``services.serve.build_server``), at the published width of each
model; weights and data come from seeds.  A phase that fails raises: the
exit code is non-zero and the result line is never printed.  Without a
TPU the device phase fails (``core.backend.NoAcceleratorError``) — there
is no CPU pass.

Every phase prints one JSON line (``{"phase": ..., "passed": true,
"wall_s": ..., "cache": {...}}``; ``cache`` counts this phase's lookups,
hits and writes in jax's persistent compilation cache).  The LAST line of
stdout is the result::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

``FULL`` is what the command line runs.  ``TOY`` exists for the CPU
rehearsal of the control flow (tests, docs in .claude/skills/verify):
phase functions take a ``Sizes`` and a device name, ``main`` always
passes ``FULL`` and ``"tpu"``.
"""

from __future__ import annotations

import argparse
import collections
import contextlib
import dataclasses
import gc
import http.client
import json
import os
import sys
import tempfile
import threading
import time
from typing import Dict, List, Optional, Tuple

import bench  # the mid LM and its serving stream are defined there

REPO = os.path.dirname(os.path.abspath(__file__))
MODELS = os.path.join(REPO, "znicz_tpu", "models")
SEED = 1234
ALEXNET_EPOCHS = 2
SERVE_REQUESTS = 8
KERNEL_MARK = "tpu_custom_call"  # a Pallas kernel that went through Mosaic


class SmokeFailure(AssertionError):
    """A phase's check did not hold."""


def _check(ok, message: str) -> None:
    if not ok:
        raise SmokeFailure(message)


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Everything a phase may shrink.  Model WIDTHS are not here: AlexNet
    runs as its model file declares it, the LM's width rides in ``lm``."""

    # loader overrides (synthetic stand-in sizes; {} = the model file's own)
    alexnet_loader: Dict[str, int]
    zoo_loader: Dict[str, int]
    lm: Dict[str, int]  # vocab / d_model / n_layers / n_heads
    lm_seq: int
    lm_batch: int
    lm_steps: int
    serve_max_seq: int
    serve_slots: int
    serve_block: int
    serve_lens: Tuple[int, ...]
    serve_new: int
    dp_batch: int  # --multichip: global batch, one step per epoch
    dp_steps: int


FULL = Sizes(
    alexnet_loader={},  # minibatch 128, 4 train + 1 valid step per epoch
    zoo_loader={},
    lm=dict(bench.LM_MID),
    lm_seq=bench.LM_T,
    lm_batch=8,
    lm_steps=3,
    serve_max_seq=256,
    serve_slots=bench.LM_MID_B,
    serve_block=bench.LM_SERVE_PAGED_BLOCK,
    serve_lens=bench.LM_SERVE_LENS,
    serve_new=bench.LM_SERVE_NEW,
    dp_batch=128,
    dp_steps=4,
)

TOY = Sizes(
    alexnet_loader={"minibatch_size": 8, "n_train": 16, "n_valid": 8},
    zoo_loader={"minibatch_size": 50, "n_train": 100, "n_test": 50},
    lm=dict(vocab=64, d_model=32, n_layers=2, n_heads=4),
    lm_seq=512,
    lm_batch=2,
    lm_steps=2,
    serve_max_seq=64,
    serve_slots=4,
    serve_block=8,
    serve_lens=(5, 12, 20, 30),
    serve_new=8,
    dp_batch=8,
    dp_steps=2,
)


# ---------------------------------------------------------------------------
# phase bookkeeping


class _CacheCounter:
    """Counts jax's persistent-compilation-cache events process-wide."""

    PREFIX = "/jax/compilation_cache/"
    NAMES = {
        "compile_requests_use_cache": "lookups",
        "cache_hits": "hits",
        "cache_misses": "writes",
    }

    def __init__(self):
        self.counts: collections.Counter = collections.Counter()

    def __enter__(self) -> "_CacheCounter":
        import jax.monitoring

        jax.monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc) -> None:
        import jax.monitoring

        jax.monitoring.unregister_event_listener(self._on_event)

    def _on_event(self, event: str, **_kwargs) -> None:
        if event.startswith(self.PREFIX):
            name = self.NAMES.get(event[len(self.PREFIX):])
            if name:
                self.counts[name] += 1

    def snapshot(self) -> Dict[str, int]:
        return {name: self.counts[name] for name in self.NAMES.values()}


@contextlib.contextmanager
def _phase(name: str, cache: _CacheCounter, report: Dict):
    """Time one phase and print its line.  ``report`` is the phase's own
    dict of facts worth printing; an exception propagates untouched."""
    before = cache.snapshot()
    t0 = time.perf_counter()
    print(f"--- phase {name}", file=sys.stderr, flush=True)
    yield
    after = cache.snapshot()
    print(
        json.dumps(
            {
                "phase": name,
                "passed": True,
                "wall_s": round(time.perf_counter() - t0, 2),
                "cache": {k: after[k] - before[k] for k in after},
                **report,
            }
        ),
        flush=True,
    )


def _run_model(
    model: str,
    device: str,
    *flags: str,
    config: Optional[Tuple[str, Dict]] = None,
):
    """One CLI run, in-process: ``python -m znicz_tpu <model> [config]
    --device ... --random-seed ...`` -> the Launcher (workflow, result).

    ``config`` is ``(node, values)``: written out as a launcher config
    module (the CLI's second positional argument, ``root.<node>.update(
    values)``) — it runs after the workflow module's import, the only
    moment an override of ``root`` survives the module's own defaults.
    Empty values mean no config file: the model file as it stands."""
    from znicz_tpu.core import prng
    from znicz_tpu.launcher import run_args

    # every run starts where a fresh ``python -m znicz_tpu`` process would:
    # the launcher seeds at initialize(), after the model file has drawn
    # its weights and synthetic data from the registry
    prng.reset()
    with tempfile.TemporaryDirectory(prefix="znicz_smoke_") as tmp:
        argv = [os.path.join(MODELS, model)]
        if config and config[1]:
            node, values = config
            argv.append(os.path.join(tmp, "smoke_config.py"))
            with open(argv[-1], "w") as f:
                f.write(
                    "from znicz_tpu.core.config import root\n"
                    f"root.{node}.update({values!r})\n"
                )
        argv += ["--device", device, "--random-seed", str(SEED), *flags]
        return run_args(argv)


def _train_losses(launcher) -> List[float]:
    return [epoch["train"]["loss"] for epoch in launcher.result.history]


def _one_batch(wf):
    """The first train minibatch, placed the way the epoch loop does."""
    import jax.numpy as jnp

    mb = next(iter(wf.loader.batches("train", shuffle=False)))
    put = wf.parallel.shard_batch if wf.parallel is not None else jnp.asarray
    x = put(mb.data)
    y = x if wf.target == "input" else put(wf._batch_target(mb))
    return x, y, put(mb.mask)


def _step_text(wf, batch) -> str:
    """The workflow's train step, lowered on a real batch."""
    import jax

    x, y, mask = batch
    return (
        jax.jit(wf.train_step_fn)
        .lower(wf.state, x, y, mask, 1.0, wf._ctx)
        .as_text()
    )


def _require_kernel(text: str, what: str) -> None:
    _check(
        KERNEL_MARK in text,
        f"{what}: no {KERNEL_MARK} in the lowered step — the kernel was "
        "interpreted or the jnp twin was selected",
    )


def _finite(values) -> bool:
    import numpy as np

    return bool(np.all(np.isfinite(np.asarray(values, np.float64))))


# ---------------------------------------------------------------------------
# phases


def phase_device(device: str, report: Dict):
    """The platform asked for, or an error; never whatever jax finds."""
    import importlib.metadata

    import jax
    import jaxlib

    from znicz_tpu.core import backend

    devices = backend.require(device)
    _check(
        devices[0].platform == device,
        f"wanted platform {device!r}, got {devices[0].platform!r}",
    )
    report.update(
        platform=devices[0].platform,
        kind=devices[0].device_kind,
        count=len(devices),
        jax=jax.__version__,
        jaxlib=jaxlib.__version__,
        libtpu=importlib.metadata.version("libtpu"),
        compile_cache_dir=backend.enable_compile_cache(),
    )
    return devices


def phase_native(report: Dict) -> None:
    """Build the batch assembler from native/batch_assembler.cc (keyed by
    the source's content); on the smoke path a failed build is a failure."""
    from znicz_tpu.loader import native

    _check(
        native.available(),
        "native batch assembler did not build (see the warning above); "
        "the loaders would have served this run from the numpy path",
    )
    report.update(batch_assembly="native")


def phase_train(sizes: Sizes, device: str, report: Dict) -> None:
    """AlexNet at its published geometry through the CLI's own entry, on
    the synthetic loader (the u8 -> device -> normalise path of real
    data): losses fall, params live on the device, the snapshot reloads."""
    import jax
    import numpy as np

    from znicz_tpu.models import alexnet
    from znicz_tpu.nn.train_state import TrainState
    from znicz_tpu.workflow.snapshotter import load_snapshot

    with tempfile.TemporaryDirectory(prefix="znicz_smoke_") as tmp:
        launcher = _run_model(
            "alexnet.py", device,
            "--stop-after", str(ALEXNET_EPOCHS),
            "--snapshot-dir", tmp,
            config=("alexnet.loader", sizes.alexnet_loader),
        )
        wf = launcher.workflow
        losses = _train_losses(launcher)
        _check(
            len(losses) == ALEXNET_EPOCHS,
            f"wanted {ALEXNET_EPOCHS} epochs, ran {len(losses)}",
        )
        all_losses = [
            m["loss"] for epoch in launcher.result.history
            for m in epoch.values()
        ]
        _check(_finite(all_losses), f"non-finite loss in {all_losses}")
        _check(
            losses[-1] < losses[0],
            f"train loss did not fall over {len(losses)} epochs: {losses}",
        )
        # the geometry the model file declares, not a shrunken copy
        declared = [
            spec["->"].get("n_kernels", spec["->"].get("output_sample_shape"))
            for spec in alexnet.DEFAULTS["layers"]
            if spec["type"] in ("conv_relu", "all2all_relu", "softmax")
        ]
        built = [
            p["weights"].shape[-1] for p in wf.state.params if "weights" in p
        ]
        _check(
            built == declared
            and tuple(wf.loader.sample_shape) == (227, 227, 3),
            f"AlexNet built as {wf.loader.sample_shape} -> {built}, "
            f"declared {declared}",
        )
        leaves = jax.tree_util.tree_leaves(wf.state.params)
        _check(
            all(d.platform == device for leaf in leaves for d in leaf.devices()),
            f"parameters are not all resident on the {device} device",
        )
        # both conv -> norm stages run the fused tail's kernels
        _require_kernel(
            _step_text(wf, _one_batch(wf)), "AlexNet step (fused conv tails)"
        )
        path = wf.snapshotter.best_path
        state, host = load_snapshot(path)  # checks the sidecar's digest
        state = TrainState(*state)
        saved = jax.tree_util.tree_leaves(state.params)
        _check(
            [s.shape for s in saved] == [p.shape for p in leaves]
            and all(_finite(s) for s in saved),
            "reloaded snapshot does not hold the model's parameters",
        )
        if int(state.step) == int(wf.state.step):
            # best == last epoch: the file holds exactly the live state
            _check(
                all(
                    np.array_equal(np.asarray(s), np.asarray(p))
                    for s, p in zip(saved, leaves)
                ),
                "reloaded snapshot differs from the state it was taken of",
            )
        report.update(
            train_losses=losses,
            n_params=int(sum(p.size for p in leaves)),
            minibatch=wf.loader.max_minibatch_size,
            snapshot_epoch=host["decision"]["epoch"] - 1,
            snapshot_mb=round(os.path.getsize(path) / 1e6, 1),
        )


def _kernel_twin_agrees(wf, twin, batch, *, rtol: float, atol: float) -> float:
    """One step of the kernel workflow against its jnp twin on the same
    state and batch, both at full f32 matmul precision (what the golden
    tests in tests/test_pallas.py compare under); returns max |diff|."""
    import jax
    import numpy as np

    x, y, mask = batch
    with jax.default_matmul_precision("highest"):
        got, _ = jax.jit(wf.train_step_fn)(wf.state, x, y, mask, 1.0, wf._ctx)
        want, _ = jax.jit(twin.train_step_fn)(
            wf.state, x, y, mask, 1.0, twin._ctx
        )
    worst = 0.0
    for g, w in zip(
        jax.tree_util.tree_leaves(got.params),
        jax.tree_util.tree_leaves(want.params),
    ):
        g, w = np.asarray(g), np.asarray(w)
        np.testing.assert_allclose(g, w, rtol=rtol, atol=atol)
        worst = max(worst, float(np.max(np.abs(g - w))))
    return worst


def phase_zoo_som(sizes: Sizes, device: str, report: Dict) -> None:
    from znicz_tpu.models import kohonen

    launcher = _run_model(
        "kohonen.py", device, "--stop-after", "1",
        config=("kohonen.loader", sizes.zoo_loader),
    )
    wf = launcher.workflow
    losses = _train_losses(launcher)
    _check(_finite(losses), f"SOM loss not finite: {losses}")
    batch = _one_batch(wf)
    _require_kernel(_step_text(wf, batch), "Kohonen SOM step")
    # the twin: the same workflow with the jnp update, built from the
    # config run_args left in root and the launcher's own override
    twin = kohonen.build_workflow(
        impl="xla", decision_config={"max_epochs": 1}
    )
    twin.initialize(seed=SEED)
    worst = _kernel_twin_agrees(wf, twin, batch, rtol=1e-4, atol=1e-5)
    report.update(som_loss=losses, som_twin_max_abs_diff=worst)


def phase_zoo_rbm(sizes: Sizes, device: str, report: Dict) -> None:
    # two epochs: the hardware PRNG has no twin to compare a step with,
    # so the check is that reconstruction error falls
    launcher = _run_model(
        "mnist_rbm.py", device, "--stop-after", "2",
        config=("mnist_rbm.loader", sizes.zoo_loader),
    )
    wf = launcher.workflow
    losses = _train_losses(launcher)
    _check(_finite(losses), f"RBM reconstruction error not finite: {losses}")
    _check(
        losses[-1] < losses[0],
        f"RBM reconstruction error did not fall: {losses}",
    )
    _require_kernel(_step_text(wf, _one_batch(wf)), "RBM CD-k step")
    report.update(rbm_reconstruction_error=losses)


def phase_zoo_lm(sizes: Sizes, device: str, report: Dict) -> None:
    """A few steps of the LM at a length where ``attention="auto"`` picks
    the flash kernel; then the kernel against its jnp twin twice over —
    forward and gradients at the step's own q/k/v shapes, and the whole
    tower's logits on two sequences.  The whole TRAIN step has no twin
    run: dense attention's saved scores do not fit the chip at this
    length."""
    from functools import partial

    import jax
    import jax.numpy as jnp
    import numpy as np

    from znicz_tpu.core import prng
    from znicz_tpu.ops import attention as att_op
    from znicz_tpu.ops.pallas.attention import flash_attention
    from znicz_tpu.workflow.transformer import init_lm_params, lm_apply

    b, t = sizes.lm_batch, sizes.lm_seq
    loader = {
        "n_train": sizes.lm_steps * b, "n_test": b,
        "seq_len": t, "minibatch_size": b,
    }
    launcher = _run_model(
        "transformer_lm.py", device, "--stop-after", "1",
        config=("transformer_lm", {**sizes.lm, "loader": loader}),
    )
    wf = launcher.workflow
    history = launcher.result.history
    losses = [m["loss"] for epoch in history for m in epoch.values()]
    _check(_finite(losses), f"LM loss not finite: {losses}")
    _check(
        wf.max_seq == t and wf.n_heads == sizes.lm["n_heads"],
        f"LM built with max_seq={wf.max_seq}, heads={wf.n_heads}",
    )
    _require_kernel(_step_text(wf, _one_batch(wf)), "LM step (flash attention)")

    head_dim = sizes.lm["d_model"] // sizes.lm["n_heads"]
    keys = jax.random.split(jax.random.key(SEED), 3)
    q, k, v = (
        jax.random.normal(kk, (b, t, sizes.lm["n_heads"], head_dim), jnp.float32)
        for kk in keys
    )

    def outputs(fn):
        def loss(q, k, v):
            return jnp.sum(jnp.sin(fn(q, k, v, causal=True)))

        return jax.jit(
            lambda q, k, v: (
                fn(q, k, v, causal=True),
                *jax.grad(loss, argnums=(0, 1, 2))(q, k, v),
            )
        )(q, k, v)

    with jax.default_matmul_precision("highest"):
        got = outputs(flash_attention)
        want = outputs(att_op.dot_product_attention)
    worst = 0.0
    for g, w in zip(got, want):
        g, w = np.asarray(g), np.asarray(w)
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-4)
        worst = max(worst, float(np.max(np.abs(g - w))))

    # the tower end to end, on fresh weights (the run above has trained
    # — at the workflow's default lr 0.1 this width does not converge)
    prng.seed_all(SEED)
    cfg = sizes.lm
    params = init_lm_params(
        cfg["vocab"], cfg["d_model"], cfg["n_layers"], cfg["n_heads"],
        max_seq=t,
    )
    tokens = jnp.asarray(_one_batch(wf)[0][:2], jnp.int32)
    with jax.default_matmul_precision("highest"):
        logits, twin_logits = (
            np.asarray(
                jax.jit(
                    partial(lm_apply, n_heads=cfg["n_heads"], attention_fn=fn)
                )(params, tokens)
            )
            for fn in (wf._attention_fn(), att_op.dot_product_attention)
        )
    np.testing.assert_allclose(logits, twin_logits, rtol=1e-4, atol=1e-4)
    report.update(
        lm_losses=losses, lm_tokens_per_step=b * t,
        flash_twin_max_abs_diff=worst,
        logits_twin_max_abs_diff=float(np.max(np.abs(logits - twin_logits))),
    )


def _post_generate(port: int, prompt: List[int], max_new: int) -> Dict:
    """One ``POST /generate``; returns the streamed tokens + done record."""
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=600)
    try:
        conn.request(
            "POST", "/generate",
            body=json.dumps({"prompt": prompt, "max_new_tokens": max_new}),
        )
        resp = conn.getresponse()
        _check(resp.status == 200, f"/generate -> HTTP {resp.status}")
        tokens, done = [], None
        while True:
            line = resp.readline()
            if not line:
                break
            record = json.loads(line)
            if "token" in record:
                tokens.append(record["token"])
            elif record.get("done"):
                done = record
        _check(done is not None, "stream ended without a done record")
        return {"tokens": tokens, "done": done}
    finally:
        conn.close()


def phase_serve(sizes: Sizes, report: Dict) -> None:
    """The mid LM behind PagedDecodeEngine -> ServingFrontDoor -> the HTTP
    server, on loopback: streamed completions equal greedy ``generate()``
    token for token, and the stream compiles nothing after warm-up.

    Greedy equality needs both sides to round alike, so — like the CPU
    tests this golden comes from — the phase runs at full f32 matmul
    precision (process-wide: the engine thread traces its own programs)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from znicz_tpu.core import prng
    from znicz_tpu.services import serve as serve_mod
    from znicz_tpu.services.engine import PagedDecodeEngine
    from znicz_tpu.services.errors import EngineClosedError
    from znicz_tpu.services.frontdoor import ServingFrontDoor
    from znicz_tpu.workflow.generate import generate
    from znicz_tpu.workflow.transformer import init_lm_params

    cfg = sizes.lm
    eos = 0
    precision = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", "highest")
    door = server = None
    try:
        prng.seed_all(95)  # bench.py's serving weights
        params = init_lm_params(
            cfg["vocab"], cfg["d_model"], cfg["n_layers"], cfg["n_heads"],
            max_seq=sizes.serve_max_seq,
        )

        def factory():
            return PagedDecodeEngine(
                params, n_heads=cfg["n_heads"], eos_id=eos,
                batch_size=sizes.serve_slots, admit_every=8,
                max_seq=sizes.serve_max_seq, block_size=sizes.serve_block,
            )

        door = ServingFrontDoor(
            factory, max_pending=4 * SERVE_REQUESTS,
            default_deadline_s=600.0,
        )
        server = serve_mod.build_server(
            directory=tempfile.gettempdir(), port=0, frontdoor=door
        )
        port = server.server_address[1]
        threading.Thread(target=server.serve_forever, daemon=True).start()

        gen = np.random.default_rng(12)
        prompts = [
            gen.integers(
                1, cfg["vocab"], (sizes.serve_lens[j % len(sizes.serve_lens)],)
            ).astype(np.int32).tolist()
            for j in range(SERVE_REQUESTS)
        ]
        # warm-up: one request per prompt length, one at a time — the
        # shortest and the longest between them reach every decode window
        for prompt in prompts[: len(sizes.serve_lens)]:
            _post_generate(port, prompt, sizes.serve_new)
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
        conn.request("GET", "/healthz")
        health = conn.getresponse()
        health_body = health.read()
        conn.close()
        _check(
            health.status == 200,
            f"/healthz -> HTTP {health.status}: {health_body[:200]!r}",
        )
        warm = door.engine.compile_stats()

        results: List[Optional[Dict]] = [None] * len(prompts)
        errors: List[BaseException] = []

        def client(i: int) -> None:
            try:
                results[i] = _post_generate(port, prompts[i], sizes.serve_new)
            except BaseException as exc:  # re-raised on the main thread
                errors.append(exc)

        clients = [
            threading.Thread(target=client, args=(i,), daemon=True)
            for i in range(len(prompts))
        ]
        for c in clients:
            c.start()
        for c in clients:
            c.join(timeout=600)
        if errors:
            raise errors[0]
        _check(
            all(r is not None for r in results),
            "a /generate client did not finish inside its time limit",
        )
        after = door.engine.compile_stats()
        jit_keys = [k for k in warm if k.endswith("_jit_entries")]
        _check(
            all(after[k] == warm[k] for k in jit_keys)
            and after["n_programs"] == warm["n_programs"],
            f"the stream compiled after warm-up: {warm} -> {after}",
        )

        for prompt, result in zip(prompts, results):
            want = np.asarray(
                generate(
                    params, jnp.asarray(prompt, jnp.int32)[None],
                    n_heads=cfg["n_heads"], max_new_tokens=sizes.serve_new,
                    eos_id=eos,
                )
            )[0][len(prompt):]
            hit = np.where(want == eos)[0]
            if len(hit):
                want = want[: hit[0] + 1]
            done = result["done"]
            _check(
                done["finish_reason"] in ("eos", "budget")
                and done["n_new"] == len(result["tokens"]),
                f"bad done record: {done}",
            )
            _check(
                result["tokens"] == want.tolist(),
                f"prompt of {len(prompt)}: streamed {result['tokens']} "
                f"!= generate() {want.tolist()}",
            )
        report.update(
            requests=len(prompts),
            prompt_lens=list(sizes.serve_lens),
            tokens_streamed=sum(len(r["tokens"]) for r in results),
            engine_programs=after["n_programs"],
            engine_jit_entries={k: after[k] for k in jit_keys},
            compiled_after_warmup=0,
            matmul_precision="highest",
        )
    finally:
        if server is not None:
            serve_mod.shutdown_gracefully(server, door, grace_s=10.0)
            server.server_close()
        elif door is not None:
            door.close(drain=False)
        jax.config.update("jax_default_matmul_precision", precision)
    try:
        door.submit([1, 2], 4)
    except EngineClosedError:
        pass
    else:
        raise SmokeFailure("the front door still takes requests after close")


def phase_multichip(sizes: Sizes, device: str, report: Dict) -> None:
    """Data-parallel AlexNet over every chip against the same seed and
    global batch on a one-device mesh, in this process: per-step losses
    agree, params are replicated, the batch is split, every chip works."""
    import jax
    import numpy as np

    n = len(jax.devices())
    batch = sizes.dp_batch
    loader = {"minibatch_size": batch, "n_train": batch, "n_valid": batch}
    flags = ("--stop-after", str(sizes.dp_steps))  # one step per epoch
    config = ("alexnet.loader", loader)
    one = _run_model(
        "alexnet.py", device, *flags, "--mesh", "data=1", config=config
    )
    many = _run_model(
        "alexnet.py", device, *flags, "--data-parallel", config=config
    )
    wf = many.workflow
    _check(
        wf.parallel is not None and wf.parallel.n_data == n,
        f"--data-parallel built a data axis of "
        f"{getattr(wf.parallel, 'n_data', None)} over {n} devices",
    )
    # bf16 activations, and the per-shard convolutions and the gradient
    # all-reduce sum in another order; measured 5e-5 on four v5e chips
    rtol = 5e-3
    losses = {
        "one_device": {
            split: [e[split]["loss"] for e in one.result.history]
            for split in ("train", "valid")
        },
        f"{n}_devices": {
            split: [e[split]["loss"] for e in many.result.history]
            for split in ("train", "valid")
        },
    }
    for split in ("train", "valid"):
        a, b = (losses[k][split] for k in losses)
        _check(_finite(a + b), f"non-finite {split} loss: {a} / {b}")
        np.testing.assert_allclose(b, a, rtol=rtol)
    leaves = jax.tree_util.tree_leaves(wf.state.params)
    for leaf in leaves:
        _check(
            leaf.sharding.is_fully_replicated
            and len({s.device for s in leaf.addressable_shards}) == n
            and all(s.data.shape == leaf.shape for s in leaf.addressable_shards),
            f"a parameter of shape {leaf.shape} is not replicated over "
            f"{n} devices: {leaf.sharding}",
        )
    x, _, _ = _one_batch(wf)
    shard_rows = {s.device.id: s.data.shape[0] for s in x.addressable_shards}
    _check(
        len(shard_rows) == n
        and all(rows == batch // n for rows in shard_rows.values()),
        f"the batch of {batch} is not split {n} ways: {shard_rows}",
    )
    param_bytes = sum(p.size * p.dtype.itemsize for p in leaves)
    peaks = {
        d.id: (d.memory_stats() or {}).get("peak_bytes_in_use")
        for d in jax.devices()
    }
    _check(
        all(p is not None and p > param_bytes for p in peaks.values()),
        f"a device never held the {param_bytes} B of parameters plus "
        f"activations — peak bytes in use per device: {peaks}",
    )
    compiled = (
        wf._train_step.lower(wf.state, *_one_batch(wf), 1.0, wf._acc_init(), wf._ctx)
        .compile()
        .as_text()
    )
    _check(
        "all-reduce" in compiled,
        "no all-reduce in the compiled data-parallel train step",
    )
    report.update(
        losses=losses,
        loss_rtol=rtol,
        max_rel_diff=max(
            abs(b - a) / abs(a)
            for split in ("train", "valid")
            for a, b in zip(*(losses[k][split] for k in losses))
        ),
        batch_rows_per_device=shard_rows,
        peak_bytes_in_use=peaks,
        param_bytes=param_bytes,
        all_reduce_in_step=True,
    )


# ---------------------------------------------------------------------------


def main(argv=None, sizes: Sizes = FULL) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument(
        "--multichip", action="store_true",
        help="run ONLY data-parallel AlexNet over all chips and the "
             "one-device run it is compared with",
    )
    args = parser.parse_args(argv)
    device = "tpu"
    t0 = time.perf_counter()
    with _CacheCounter() as cache:
        report: Dict = {}
        with _phase("device", cache, report):
            devices = phase_device(device, report)
        if args.multichip:
            _check(
                len(devices) > 1,
                f"--multichip needs more than one chip, found {len(devices)}",
            )
            phases = [
                ("multichip", lambda r: phase_multichip(sizes, device, r))
            ]
        else:
            phases = [
                ("native", phase_native),
                ("train", lambda r: phase_train(sizes, device, r)),
                ("zoo_som", lambda r: phase_zoo_som(sizes, device, r)),
                ("zoo_rbm", lambda r: phase_zoo_rbm(sizes, device, r)),
                ("zoo_lm", lambda r: phase_zoo_lm(sizes, device, r)),
                ("serve", lambda r: phase_serve(sizes, r)),
            ]
        for name, fn in phases:
            report = {}
            with _phase(name, cache, report):
                fn(report)
            gc.collect()  # the phase's workflow and its device arrays
        totals = cache.snapshot()
    print(
        json.dumps(
            {"total_wall_s": round(time.perf_counter() - t0, 2),
             "cache": totals}
        ),
        flush=True,
    )
    print(
        json.dumps(
            {
                "ok": True,
                "device": {
                    "platform": devices[0].platform,
                    "kind": devices[0].device_kind,
                    "count": len(devices),
                },
            }
        ),
        flush=True,
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
