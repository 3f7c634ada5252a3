"""Driver ``train_epochs``: a CNN model file trained the way the launcher
trains it, whole epochs through ``Workflow.run_epoch()``.

What ``python -m znicz_tpu <model file> --data-dir <packed> [--data-parallel]``
runs, with ``run_epoch()`` called for the window where the launcher calls
``run()``: the model file's own ``build_workflow()``, ``ImageNetLoader``
over a memory-mapped packed file, native crops on the host, prefetch,
the compiled step, the epoch's metric sync.

Traffic parameters (a key this driver does not know is an error):

- ``loader_mode``: ``"streamed"`` (the model file's path: uint8 crops cross
  the link every step) or ``"resident"`` (``ImageNetLoader(device_resident=
  True)``, the packed pool in HBM, a library-only mode today);
- ``per_chip_batch``, ``data_parallel`` (``DataParallel()`` over every chip,
  what ``--data-parallel`` passes; the global batch is per_chip_batch x chips);
- ``n_train_images``: size of the packed stand-in, one epoch;
- ``epoch_dispatch``, ``epoch_sync``: the workflow's own arguments;
- ``check_steps``, ``reference_rows``, ``limits``: how ``correct`` is decided;
- ``trace_s``: seconds of device trace in a ``--trace 1`` run (0.6).
"""

from __future__ import annotations

import math
import os
import time

import numpy as np

from harness import packed, registry, weights, work
from harness.checks import Checks, worst_leaf_difference, worst_leaf_gap
from harness.loading import load_module

KNOWN = {
    "loader_mode", "per_chip_batch", "data_parallel", "n_train_images",
    "epoch_dispatch", "epoch_sync", "check_steps", "reference_rows", "limits",
    "trace_s",
}
TRAIN_PHASES = "znicz_train_phase_seconds"


class _FirstSteps:
    """Stands in for the workflow's compiled step during the warm-up
    epoch and passes every call through to it; of the first ``n`` calls it
    keeps, on the host, what the reference needs to follow them: the batch
    as fed, the step's own [loss, grad norm], and the state it returned."""

    def __init__(self, step, n: int):
        self.step, self.n, self.records = step, n, []

    def __call__(self, state, x, y, mask, lr_scale, acc, ctx):
        import jax

        out = self.step(state, x, y, mask, lr_scale, acc, ctx)
        i = len(self.records)
        if i < self.n:
            new_state, _, watch = out
            record = {
                "x": jax.device_get(x), "y": jax.device_get(y),
                "mask": jax.device_get(mask), "lr_scale": float(lr_scale),
                "watch": jax.device_get(watch),
            }
            if i == 0:
                record["velocity"] = jax.device_get(new_state.velocity)
            if i == self.n - 1:
                record["params"] = jax.device_get(new_state.params)
            self.records.append(record)
        return out


def _build(run, traffic, data_dir, batch):
    """The workflow, as the entry point builds it."""
    from znicz_tpu.core.config import root
    from znicz_tpu.models import alexnet

    kwargs = {}
    if traffic["data_parallel"]:
        from znicz_tpu.parallel import DataParallel

        kwargs["parallel"] = DataParallel()
    for key in ("epoch_dispatch", "epoch_sync"):
        if key in traffic:
            kwargs[key] = traffic[key]
    root.alexnet.loader.update({"data_dir": data_dir, "minibatch_size": batch})
    if traffic["loader_mode"] == "streamed":
        return alexnet.build_workflow(**kwargs)
    if traffic["loader_mode"] != "resident":
        raise ValueError(f"loader_mode {traffic['loader_mode']!r}")
    # the resident pool has no config key or flag yet (PERF.md, Open
    # questions): the same layers and recipe around a loader built here
    from znicz_tpu.loader import ImageNetLoader
    from znicz_tpu.models import effective_config, merge_workflow_kwargs
    from znicz_tpu.workflow import StandardWorkflow

    cfg = effective_config(root.alexnet, alexnet.DEFAULTS)
    loader = ImageNetLoader(
        data_dir, crop_size=cfg.loader.get("image_size"),
        pack_size=cfg.loader.get("pack_size"), minibatch_size=batch,
        device_resident=True,
    )
    return StandardWorkflow(
        loader, cfg.get("layers"),
        **merge_workflow_kwargs(
            {
                "decision_config": cfg.decision.to_dict(),
                "lr_policy": cfg.get("lr_policy"),
                "compute_dtype": cfg.get("compute_dtype"),
                "name": "AlexNetWorkflow",
            },
            kwargs,
        ),
    )


def _place_state(wf, params, key):
    """The benchmark's seeded weights and dropout key as the workflow's
    fresh train state, placed the way ``initialize()`` places its own."""
    from znicz_tpu.nn.train_state import TrainState

    state = TrainState.create(params, key)
    if wf.parallel is not None:
        state = wf.parallel.shard_state(state)
    wf.state = state
    wf._host_step = 0


def dropout_masks(cfg, key, step: int, rows: int):
    """The keep-masks of one step: the model splits ``fold_in(key, step)``
    into one key per layer and draws ``bernoulli(key_i, keep, shape)`` for
    dropout layer i.  Drawn here from the key alone, so the reference sees
    the masks as data."""
    import jax

    layers = cfg["layers"]
    split = jax.random.split(jax.random.fold_in(key, step), len(layers))
    width, masks = None, []
    for i, spec in enumerate(layers):
        if spec["type"] == "fc":
            width = spec["n"]
        elif spec["type"] == "dropout":
            masks.append(
                jax.random.bernoulli(
                    split[i], 1.0 - spec["ratio"], (rows, width)
                )
            )
    return masks


def _crops(record, pool, crop: int):
    """A resident loader's [B, 4] payload (row, oy, ox, flip) as the
    uint8 crops the step cuts from the pool."""
    out = np.empty((len(record), crop, crop, 3), np.uint8)
    for i, (row, oy, ox, flip) in enumerate(record):
        img = pool[row, oy: oy + crop, ox: ox + crop]
        out[i] = img[:, ::-1] if flip else img
    return out


def _host_leaves(tree) -> dict:
    """{"<layer>.<name>": float64 array} for every parameterised leaf."""
    return {
        f"{i}.{name}": np.asarray(leaf, np.float64)
        for i, layer in enumerate(tree)
        for name, leaf in layer.items()
    }


def _norms(leaves: dict) -> dict:
    return {k: float(np.linalg.norm(v)) for k, v in leaves.items()}


def follow(cfg, traffic, params0, key, records, pool=None, *, cast=None):
    """The plain reference over the recorded first steps.  Returns per
    step its loss, and leaf by leaf the first gradient and the
    parameters' change after the last step."""
    import jax
    import jax.numpy as jnp

    ref = load_module("reference", "alexnet")
    kwargs = {"rows": traffic.get("reference_rows", 128)}
    if cast is not None:
        kwargs["cast"] = cast
    params = params0
    velocity = jax.tree_util.tree_map(jnp.zeros_like, params0)
    losses, first_grad = [], None
    for step, rec in enumerate(records):
        x = rec["x"] if rec["x"].ndim == 4 else _crops(
            rec["x"], pool, cfg["input_shape"][0]
        )
        masks = dropout_masks(cfg, key, step, x.shape[0])
        loss, grads = ref.loss_and_grads(
            cfg, params, jnp.asarray(x), jnp.asarray(rec["y"]), masks, **kwargs
        )
        losses.append(float(loss))
        if step == 0:
            first_grad = _host_leaves(jax.device_get(grads))
        params, velocity = ref.sgd_step(
            cfg, params, velocity, grads, rec["lr_scale"]
        )
    delta = jax.tree_util.tree_map(
        lambda a, b: np.asarray(a, np.float64) - np.asarray(b, np.float64),
        jax.device_get(params), jax.device_get(params0),
    )
    return {
        "losses": losses, "first_grad": first_grad,
        "delta": _host_leaves(delta),
    }


def program_readings(cfg, params0, records) -> dict:
    """The same numbers from what the program's own steps returned: each
    step's loss, the first gradient as the optimizer got it (worked out
    from the velocity after one step: v1 = -lr (g + decay w0)), and the
    parameters' change after the last recorded step."""
    opt = cfg["optimizer"]
    host0 = [
        {k: np.asarray(v, np.float64) for k, v in layer.items()}
        for layer in params0
    ]
    grad = []
    for w0, v1 in zip(host0, records[0]["velocity"]):
        layer = {}
        for name in w0:
            bias = name == "bias"
            lr = opt["learning_rate_bias" if bias else "learning_rate"]
            wd = opt["weights_decay_bias" if bias else "weights_decay"]
            lr *= records[0]["lr_scale"]
            layer[name] = -np.asarray(v1[name], np.float64) / lr - wd * w0[name]
        grad.append(layer)
    delta = [
        {k: np.asarray(layer[k], np.float64) - w0[k] for k in w0}
        for layer, w0 in zip(records[-1]["params"], host0)
    ]
    return {
        "losses": [float(r["watch"][0]) for r in records],
        "first_grad": _host_leaves(grad),
        "delta": _host_leaves(delta),
    }


def compare(checks: Checks, got: dict, want: dict, limits: dict) -> None:
    """Six kinds of number, each with a limit of its own.  The norm gaps
    are held against the faults they are there to catch (a part of the
    batch left out moves the loss; a step that returns its state
    unchanged makes the change's norm gap 1).  Rounding that is unbiased
    hardly moves a norm, so the lower-precision control is caught by the
    two difference numbers: the norm of (program - reference), leaf by
    leaf."""
    for i, (a, b) in enumerate(zip(got["losses"], want["losses"])):
        checks.at_most(
            f"loss_step{i}_rel_gap", abs(a - b) / abs(b), limits["loss_rel_gap"]
        )
    for what, name in (("first_grad", "first_grad"), ("delta", "param_change")):
        checks.at_most(
            f"{name}_norm_worst_leaf_gap",
            worst_leaf_gap(_norms(got[what]), _norms(want[what])),
            limits[f"{name}_norm_worst_leaf_gap"],
        )
        checks.at_most(
            f"{name}_worst_leaf_rel_diff",
            worst_leaf_difference(got[what], want[what]),
            limits[f"{name}_worst_leaf_rel_diff"],
        )


def setup(run):
    """Everything before the window.  Returns the live pieces."""
    import jax

    from znicz_tpu.core import prng

    cfg, traffic = run.config, run.traffic
    unknown = set(traffic) - KNOWN
    if unknown:
        raise ValueError(f"train_epochs does not know {sorted(unknown)}")
    marks = [("start", time.perf_counter())]
    n_chips = run.chips if traffic["data_parallel"] else 1
    batch = traffic["per_chip_batch"] * n_chips
    if traffic["n_train_images"] % batch:
        raise ValueError("n_train_images must be whole batches")
    data_dir = packed.ensure(
        run.cache_dir, traffic["n_train_images"], cfg["pack_size"],
        cfg["n_classes"],
    )
    # the model file draws from the prng registry as it is built; the
    # shuffle, the crops and the flips draw from it all through the run
    marks.append(("packed file", time.perf_counter()))
    prng.reset()
    prng.seed_all(run.seed)
    wf = _build(run, traffic, data_dir, batch)
    wf.initialize(seed=run.seed)
    marks.append(("model file + initialize", time.perf_counter()))
    built = [
        tuple(p["weights"].shape) for p in wf.state.params if "weights" in p
    ]
    declared = [w for _, w, _, _ in weights.cnn_shapes(cfg)]
    if built != declared:
        raise RuntimeError(
            f"the model file builds {built}; {cfg['name']}.json says {declared}"
        )
    params0 = weights.cnn_weights(cfg, run.seed)
    key = jax.random.fold_in(weights.seed_key(run.seed), 3)
    # the step donates its state, the key with it: keep the key's words
    key_data = np.asarray(jax.random.key_data(key))
    _place_state(wf, params0, key)
    host_params0 = jax.device_get(params0)
    del params0
    marks.append(("seeded weights", time.perf_counter()))

    # the warm-up epoch: compiles the step, fills the page cache, and its
    # first steps are the ones the reference follows
    spy = _FirstSteps(wf._train_step, traffic["check_steps"])
    wf._train_step = spy
    try:
        wf.run_epoch()
        wf.sync_epoch()
    finally:
        wf._train_step = spy.step
    jax.block_until_ready(wf.state.params)
    marks.append(("warm-up epoch", time.perf_counter()))
    print(
        "set-up inside the driver: " + ", ".join(
            f"{name} {t - marks[i][1]:.2f} s"
            for i, (name, t) in enumerate(marks[1:])
        ),
        flush=True,
    )
    return {
        "wf": wf, "batch": batch, "n_chips": n_chips, "key_data": key_data,
        "params0": host_params0, "records": spy.records,
        "step_program": "jit_" + getattr(spy.step, "__name__", "train_acc"),
    }


def window(run, live, capture=None) -> dict:
    import jax

    wf, traffic = live["wf"], run.traffic
    steps_per_epoch = traffic["n_train_images"] // live["batch"]
    before = registry.read()
    epochs = 0
    t0 = time.perf_counter()
    while True:
        if capture is not None and epochs == 1:
            # a slice of the second epoch: steps in steady state, with
            # the loader, the prefetch and the dispatch all running
            capture.run_for(0.3, float(traffic.get("trace_s", 0.6)))
        wf.run_epoch()
        epochs += 1
        if time.perf_counter() - t0 >= run.seconds:
            break
    wf.sync_epoch()
    jax.block_until_ready(wf.state.params)
    elapsed = time.perf_counter() - t0
    if capture is not None:
        capture.join()
    delta = registry.Delta(before, registry.read())
    bad = sum(
        delta.value("znicz_train_anomalies_total", type=kind) or 0
        for kind in ("non_finite_loss", "non_finite_grad_norm")
    )
    last = wf.decision.history[-1] if getattr(wf.decision, "history", None) else {}
    train_loss = (last.get("train") or {}).get("loss")
    if train_loss is not None and not math.isfinite(train_loss):
        bad = max(bad, steps_per_epoch)
    return {
        "elapsed_s": elapsed, "epochs": epochs,
        "steps": epochs * steps_per_epoch,
        "images": epochs * traffic["n_train_images"],
        "failed": int(bad), "delta": delta, "last_train_loss": train_loss,
    }


def decide_correct(run, live) -> Checks:
    """After the window, with the program's state freed: the reference
    follows the warm-up epoch's first steps."""
    import jax
    import jax.numpy as jnp

    cfg, traffic = run.config, run.traffic
    pool = None
    if traffic["loader_mode"] == "resident":
        pool = np.load(
            os.path.join(live["wf"].loader.data_dir, "train_images.npy"),
            mmap_mode="r",
        )
    live["wf"].state = None
    live.pop("wf")
    got = program_readings(cfg, live["params0"], live["records"])
    params0 = jax.tree_util.tree_map(jnp.asarray, live["params0"])
    key = jax.random.wrap_key_data(jnp.asarray(live["key_data"]))
    want = follow(cfg, traffic, params0, key, live["records"], pool)
    checks = Checks()
    compare(checks, got, want, traffic["limits"])
    return checks


def run(run_ctx) -> dict:
    live = setup(run_ctx)
    run_ctx.mark_setup_done()
    capture = run_ctx.new_capture() if run_ctx.trace else None
    w = window(run_ctx, live, capture)
    memory_peak = run_ctx.memory_peak_bytes()
    print(
        f"train_epochs: {w['epochs']} epochs, {w['steps']} steps of "
        f"{live['batch']} images in {w['elapsed_s']:.3f} s; last train loss "
        f"{w['last_train_loss']}",
        flush=True,
    )
    cfg = run_ctx.config
    observations = {
        "registry": w["delta"], "steps": w["steps"],
        "train_phases": w["delta"].phases(TRAIN_PHASES),
        "step_program": live["step_program"],
        "step_flops_per_chip": 3.0
        * work.cnn_forward_flops_per_image(cfg["layers"], cfg["input_shape"])
        * run_ctx.traffic["per_chip_batch"],
        "trace": capture.reduced if capture else None,
    }
    checks = decide_correct(run_ctx, live)
    return {
        "metrics": {"images_per_s": w["images"] / w["elapsed_s"]},
        "attempted": w["steps"], "failed": w["failed"], "checks": checks,
        "observations": observations, "memory_peak_bytes": memory_peak,
    }
