"""Headline benchmarks: training + serving throughput on one TPU chip.

Prints one JSON line PER SECTION:
  {"metric": "alexnet_images_per_sec", "value": N, "unit": "images/sec",
   "vs_baseline": mfu/0.35, ...}
  {"metric": "lm_tokens_per_sec", ...}
  ...
  {"metric": "bench_sections_failed", "value": K, "failed_sections": []}

Each section runs in its own try/except and emits its own
``{"metric": ...}`` or ``{"error": ..., "section": ...}`` record, so one
section's failure can never zero out the whole round.  Backend bring-up
is one probe that refuses anything but a TPU: a CPU rate is never
written under a device metric's name.

``--only <prefix>`` re-runs just the sections whose name starts with
the prefix (cheap re-runs: ``python bench.py --only lm_serve``).

``vs_baseline`` on the AlexNet record is measured
model-FLOPs-utilization relative to the BASELINE.json north-star gate
of 35% MFU (the reference itself has no published numbers to compare
against — see BASELINE.md).
"""

from __future__ import annotations

import json
import os
import sys
import time
import traceback

# ---------------------------------------------------------------------------
# shared helpers

_SECTIONS = []


def _section(name):
    """Register a bench section: ``fn(ctx) -> list-of-records``."""

    def deco(fn):
        _SECTIONS.append((name, fn))
        return fn

    return deco


def emit(rec) -> None:
    """One record, one parseable line."""
    print(json.dumps(rec), flush=True)


def _metrics_snapshot() -> dict:
    """The process-wide telemetry registry, attached to error records
    and the final summary so every round carries the serve/train
    counters and latency histograms behind it.  A ``"slo"`` entry
    (``{"type": "slo", ...}`` — self-describing next to the metric
    families) carries the lifetime SLO judgment over the same registry:
    per-target percentiles, burn rates and the breach flag that
    ``tools/znicz-slo`` gates on.  A ``"programs"`` entry (same
    self-describing shape) carries the device/compile ledger headline —
    every round records how many programs the run compiled, their total
    compile wall seconds and the per-kind split, so a compile-count
    regression is diffable round-over-round via znicz-bench-diff."""
    try:
        from znicz_tpu.observability import device, get_registry
        from znicz_tpu.observability import slo as slo_mod

        from znicz_tpu.observability.pipeline import PipelineAttribution

        snap = get_registry().snapshot()
        snap["slo"] = slo_mod.lifetime_snapshot()
        # the input-pipeline attribution verdict over the whole round
        # ({"type": "pipeline"} — self-describing like "slo", skipped
        # by the aggregator's family merge)
        snap["pipeline"] = PipelineAttribution.from_registry().attribution()
        ledger = device.ledger_snapshot()
        snap["programs"] = {
            "type": "programs",
            "count": ledger["count"],
            "engine_count": ledger["engine_count"],
            "by_kind": ledger["by_kind"],
            "compile_seconds_total": ledger["compile_seconds_total"],
        }
        return snap
    except Exception as e:
        # the record must still print even if telemetry import breaks
        print(f"metrics snapshot failed: {e!r}", file=sys.stderr)
        return {}


def _program_headline() -> dict:
    """Top-level numeric compile-ledger fields for the summary record
    (``programs_compiled`` is lower-better under znicz-bench-diff's
    name heuristic — a compile-count regression across rounds fails
    the gate)."""
    try:
        from znicz_tpu.observability import device

        # the two scalars only — ledger_snapshot() would copy every
        # entry and poll per-device memory_stats a second time per
        # record (metrics_snapshot already does that once)
        return {
            "programs_compiled": device.program_count(),
            "programs_compile_seconds": device.compile_seconds_total(),
        }
    except Exception as e:
        print(f"program headline failed: {e!r}", file=sys.stderr)
        return {}


def _init_backend(probe=None):
    """One probe of the backend; raises unless it is a TPU, so no
    section can write a CPU rate under a device metric's name.
    ``probe`` (-> device list) is injectable for the tier-1 tests."""
    if probe is None:
        from znicz_tpu.core import backend

        def probe():
            return backend.require("tpu")

    devs = probe()
    if devs[0].platform != "tpu":
        raise RuntimeError(
            f"bench.py measures the TPU; the backend came up on "
            f"{devs[0].platform!r} ({devs[0].device_kind})"
        )
    print(
        f"backend up: {devs[0].platform} {devs[0].device_kind} "
        f"x{len(devs)}",
        file=sys.stderr,
    )
    return devs


def run_sections(sections=None, only=None, emit_record=emit,
                 budget_s=None):
    """Run bench sections under per-section isolation AND a per-section
    wall-clock budget; returns the list of failed section names.
    Records flow through ``emit_record`` (one call per record) —
    injectable for the tier-1 schema test.

    Each section runs on a worker thread joined with ``budget_s``
    (default ``BENCH_SECTION_BUDGET_S`` env, 900 s): a HUNG section —
    a wedged device call, a deadlocked engine — emits its own
    ``{"error": "timeout", "section": ...}`` record and the round moves
    on instead of stalling forever.  The abandoned worker is daemonic;
    it may keep contending for the device until the process exits, so
    a timeout can degrade (not zero) the sections after it — the
    timeout record names the culprit."""
    import threading

    if budget_s is None:
        budget_s = float(os.environ.get("BENCH_SECTION_BUDGET_S", "900"))
    ctx: dict = {}
    failed = []
    for name, fn in (_SECTIONS if sections is None else sections):
        if only and not name.startswith(only):
            continue
        t0 = time.time()
        print(f"=== section {name}", file=sys.stderr)
        holder: dict = {}

        def _worker(fn=fn):
            try:
                holder["records"] = list(fn(ctx) or [])
            except Exception as e:  # reported by the join below
                holder["exc"] = e

        worker = threading.Thread(
            target=_worker, name=f"bench-{name}", daemon=True
        )
        worker.start()
        worker.join(timeout=budget_s if budget_s > 0 else None)
        if worker.is_alive():
            failed.append(name)
            emit_record(
                {
                    "error": "timeout",
                    "section": name,
                    "budget_s": budget_s,
                }
            )
            print(
                f"=== section {name} TIMED OUT after {budget_s:.0f}s "
                "(worker abandoned)",
                file=sys.stderr,
            )
            continue
        if "exc" in holder:
            e = holder["exc"]
            failed.append(name)
            traceback.print_exception(
                type(e), e, e.__traceback__, file=sys.stderr
            )
            emit_record(
                {
                    "error": type(e).__name__,
                    "section": name,
                    "detail": str(e)[:500],
                }
            )
        else:
            for rec in holder.get("records", []):
                emit_record(rec)
        print(
            f"=== section {name} done in {time.time() - t0:.1f}s",
            file=sys.stderr,
        )
    return failed


# bf16 peak FLOP/s of one chip, keyed by ``device_kind``.  Source:
# Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16 per chip).
# A kind that is not here is an error, never a default.
_PEAK_FLOPS = {"TPU v5 lite": 197e12}


def _peak_flops() -> float:
    import jax

    kind = jax.devices()[0].device_kind
    if kind not in _PEAK_FLOPS:
        raise KeyError(
            f"no peak FLOP/s on record for device_kind {kind!r}; add it "
            "to bench._PEAK_FLOPS with its source"
        )
    return _PEAK_FLOPS[kind]


def _sync(arr):
    import jax

    jax.block_until_ready(arr)


def _model_flops_per_image(layers, input_shape) -> float:
    """Analytic fwd FLOPs (2*MACs) through the declarative layer list."""
    import numpy as np

    from znicz_tpu.ops import conv as conv_op, pooling as pool_op

    shape = (1,) + tuple(input_shape)
    total = 0.0
    for spec in layers:
        kind = spec["type"]
        fwd = spec.get("->", {})
        if kind.startswith("conv"):
            out = conv_op.output_shape(
                shape, fwd["n_kernels"], fwd["kx"], fwd["ky"],
                fwd.get("sliding", (1, 1)), fwd.get("padding", (0, 0, 0, 0)),
            )
            total += (
                2.0 * out[1] * out[2] * out[3]
                * fwd["kx"] * fwd["ky"] * shape[3]
            )
            shape = out
        elif kind.endswith("pooling"):
            shape = pool_op.output_shape(
                shape, fwd["kx"], fwd["ky"], fwd.get("sliding")
            )
        elif kind.startswith("all2all") or kind == "softmax":
            n_in = int(np.prod(shape[1:]))
            n_out = int(np.prod(fwd["output_sample_shape"]))
            total += 2.0 * n_in * n_out
            shape = (1, n_out)
    return total


# ---------------------------------------------------------------------------
# training sections


@_section("alexnet_step")
def _sec_alexnet(ctx):
    t_setup = time.time()
    import jax
    import jax.numpy as jnp

    from znicz_tpu.core import prng
    from znicz_tpu.core.config import root
    from znicz_tpu.models import alexnet

    batch = int(os.environ.get("BENCH_BATCH", "1024"))
    steps = int(os.environ.get("BENCH_STEPS", "20"))
    ctx["batch"] = batch
    root.alexnet.loader.update(
        {"minibatch_size": batch, "n_train": batch, "n_valid": 0}
    )
    prng.seed_all(1234)
    wf = alexnet.build_workflow()
    wf.initialize(seed=1234)
    ctx["alex_sample_shape"] = wf.loader.sample_shape
    ctx["alex_layers"] = root.alexnet.get("layers")

    mb = next(iter(wf.loader.batches("train")))
    x = jnp.asarray(mb.data)
    y = jnp.asarray(mb.labels)
    mask = jnp.asarray(mb.mask)

    # compile + warmup (steps carry the on-device metric accumulator)
    state, acc, _w = wf._train_step(
        wf.state, x, y, mask, 1.0, wf._acc_init(), wf._ctx
    )
    state, acc, _w = wf._train_step(state, x, y, mask, 1.0, acc, wf._ctx)
    jax.block_until_ready(acc)
    print(f"setup+compile {time.time()-t_setup:.1f}s", file=sys.stderr)

    # difference two run lengths so the fixed per-run sync cost cancels
    # and only true per-step device time remains
    def timed(n):
        nonlocal state, acc
        t0 = time.time()
        for _ in range(n):
            state, acc, _w = wf._train_step(state, x, y, mask, 1.0, acc, wf._ctx)
        jax.block_until_ready(acc)
        return time.time() - t0

    timed(2)  # absorb the donated-buffer-layout recompile
    timed(2)
    # host noise is additive-positive: min over repeats per run length
    # is the robust estimator, and the 3N-vs-N difference cancels the
    # fixed sync cost
    t_short = min(timed(steps) for _ in range(3))
    t_long = min(timed(3 * steps) for _ in range(3))
    print(
        f"t_short({steps})={t_short:.3f}s t_long({3*steps})={t_long:.3f}s",
        file=sys.stderr,
    )
    dt = (t_long - t_short) / (2 * steps)  # seconds per step
    if dt <= 0:  # fell into noise; use the long run directly
        dt = t_long / (3 * steps)

    images_per_sec = batch / dt
    ctx["alexnet_images_per_sec"] = images_per_sec

    fwd_flops = _model_flops_per_image(
        ctx["alex_layers"], ctx["alex_sample_shape"]
    )
    train_flops = 3.0 * fwd_flops  # fwd + input-grad + weight-grad
    mfu = images_per_sec * train_flops / _peak_flops()
    return [
        {
            "metric": "alexnet_images_per_sec",
            "value": round(images_per_sec, 2),
            "unit": "images/sec",
            "vs_baseline": round(mfu / 0.35, 4),
            "mfu": round(mfu, 4),
            "batch": batch,
            "step_ms": round(1000 * dt, 2),
            "device": str(jax.devices()[0].device_kind),
        }
    ]


@_section("alexnet_epoch")
def _sec_epoch(ctx):
    # end-to-end epoch throughput: the production run_epoch path with
    # the loader IN the loop (shuffle, index gather, prefetch thread,
    # on-device normalize, per-epoch metric sync).  Two modes:
    #   device_resident — dataset pool in HBM, per batch only the index
    #     vector crosses host->device (the TPU-first mode for datasets
    #     that fit on-chip); this is the headline epoch number.
    #   streaming — u8 minibatches cross host->device each step (the
    #     ImageNet-at-scale mode), reported alongside the measured
    #     host->device link bandwidth.
    import jax
    import jax.numpy as jnp
    import numpy as np

    from znicz_tpu.core.config import root
    from znicz_tpu.loader.fullbatch import FullBatchLoader
    from znicz_tpu.workflow import StandardWorkflow

    batch = ctx.get("batch") or int(os.environ.get("BENCH_BATCH", "1024"))
    n_epoch_imgs = int(os.environ.get("BENCH_EPOCH_IMAGES", str(8 * batch)))
    gen = np.random.default_rng(0)
    # dtype=uint8 up front: the default int64 would transiently be 8x the
    # final array (~GBs at default sizes)
    images_u8 = gen.integers(
        0, 256, (n_epoch_imgs, 227, 227, 3), dtype=np.uint8
    )
    labels = gen.integers(0, 1000, n_epoch_imgs).astype(np.int32)

    def epoch_rate(device_resident: bool, n_epochs: int):
        e_loader = FullBatchLoader(
            {"train": images_u8},
            {"train": labels},
            minibatch_size=batch,
            normalization="range",
            normalization_kwargs={"scale": 255.0, "shift": -0.5},
            device_convert=not device_resident,
            device_resident=device_resident,
        )
        ewf = StandardWorkflow(
            e_loader,
            root.alexnet.get("layers"),
            decision_config={"max_epochs": 10000},
            compute_dtype="bfloat16",
            # deferred epoch sync: the metric fetch of epoch N rides
            # behind epoch N+1's dispatch, so the per-epoch transport
            # round trip overlaps compute (VERDICT r3 #4)
            epoch_sync="deferred",
            name="AlexNetEpochBench",
        )
        ewf.initialize(seed=7)
        ewf.run_epoch()  # compile + warmup
        ewf.sync_epoch()
        ewf.timer.reset()
        t0 = time.time()
        for _ in range(n_epochs):
            ewf.run_epoch()
        ewf.sync_epoch()  # observe the final epoch (timed: honest wall)
        wall = time.time() - t0
        # per-phase breakdown (VERDICT r3 gate: explain the epoch-vs-
        # compute-only gap): host stack+put, async scan dispatch, and the
        # blocking metric fetch — whatever wall time none of them covers
        # is untimed host work (shuffle, python loop)
        phases = {
            k: round(v["total_s"] / n_epochs, 4)
            for k, v in ewf.timer.summary().items()
        }
        phases["wall_per_epoch"] = round(wall / n_epochs, 4)
        return n_epoch_imgs * n_epochs / wall, phases

    # 15 epochs: the one blocking round trip left (the FINAL epoch's
    # deferred fetch) amortizes to ~1/15 of an epoch, and the longer run
    # averages over host jitter
    epoch_images_per_sec, epoch_phases = epoch_rate(True, 15)
    ctx["epoch_images_per_sec"] = epoch_images_per_sec
    print(
        f"epoch bench (device-resident): {epoch_images_per_sec:.0f} img/s "
        f"breakdown={epoch_phases}",
        file=sys.stderr,
    )
    streaming_images_per_sec, _ = epoch_rate(False, 1)

    # measured host->device link bandwidth: difference two chunk sizes so
    # the fixed per-round-trip sync cost cancels (same methodology as the
    # step timing above)
    def put_time(rows):
        chunk = images_u8[:rows]
        dev = jax.device_put(chunk)
        float(jnp.sum(dev.astype(jnp.float32))[None][0])  # force arrival
        t0 = time.time()
        dev = jax.device_put(chunk)
        float(jnp.sum(dev.astype(jnp.float32))[None][0])
        return chunk.nbytes, time.time() - t0

    put_time(64)  # warm both program shapes
    b_small, t_small = put_time(64)
    b_large, t_large = put_time(512)
    dt_put = t_large - t_small
    put_mbps = (
        (b_large - b_small) / dt_put / 1e6
        if dt_put > 0
        else b_large / max(t_large, 1e-9) / 1e6
    )
    print(
        f"epoch bench (streaming): {streaming_images_per_sec:.0f} img/s; "
        f"host->device link ~{put_mbps:.0f} MB/s",
        file=sys.stderr,
    )
    images_per_sec = ctx.get("alexnet_images_per_sec", 0.0)
    return [
        {
            "metric": "epoch_images_per_sec",
            "value": round(epoch_images_per_sec, 2),
            "unit": "images/sec",
            "epoch_vs_compute_only": round(
                epoch_images_per_sec / images_per_sec, 4
            ) if images_per_sec else 0.0,
            "epoch_streaming_images_per_sec": round(
                streaming_images_per_sec, 2
            ),
            "epoch_breakdown_s": epoch_phases,
            # the epoch-vs-compute gap, explained (VERDICT r3 #4): the
            # scanned epoch is ONE async dispatch; all wall time sits in
            # the blocking metric fetch = device compute (epoch images /
            # compute-only rate) + ONE host round trip.  The residual
            # below is that round trip.
            "epoch_sync_residual_s": round(
                epoch_phases.get("metrics_sync", 0.0)
                - n_epoch_imgs / images_per_sec,
                4,
            ) if images_per_sec else 0.0,
            "host_to_device_MBps": round(put_mbps, 1),
        }
    ]


@_section("imagenet_resident")
def _sec_imagenet(ctx):
    # HBM-resident ImageNet pipeline (VERDICT r3 #5): the packed 256^2
    # pool ships ONCE; per step only [B, 4] int32 (row, oy, ox, flip)
    # crosses the link and random-crop+flip+normalize run inside the
    # jitted step.  This is the TPU-first answer to a slow host link for
    # datasets that fit HBM — steady-state behaves like device-resident,
    # with real reference augmentation semantics.
    import shutil
    import tempfile

    import numpy as np

    from znicz_tpu.core.config import root
    from znicz_tpu.loader.imagenet import ImageNetLoader
    from znicz_tpu.workflow import StandardWorkflow

    batch = ctx.get("batch") or int(os.environ.get("BENCH_BATCH", "1024"))
    gen = np.random.default_rng(0)
    n_imnet = int(os.environ.get("BENCH_IMAGENET_IMAGES", "4096"))
    pack_dir = tempfile.mkdtemp(prefix="bench_imnet_")
    try:
        pool = gen.integers(0, 256, (n_imnet, 256, 256, 3), dtype=np.uint8)
        np.save(os.path.join(pack_dir, "train_images.npy"), pool)
        np.save(
            os.path.join(pack_dir, "train_labels.npy"),
            gen.integers(0, 1000, n_imnet).astype(np.int32),
        )
        with open(os.path.join(pack_dir, "mean_rgb.json"), "w") as f:
            json.dump([0.485, 0.456, 0.406], f)
        del pool

        im_loader = ImageNetLoader(
            pack_dir, crop_size=227, minibatch_size=batch,
            device_resident=True,
        )
        iwf = StandardWorkflow(
            im_loader,
            root.alexnet.get("layers"),
            decision_config={"max_epochs": 10000},
            compute_dtype="bfloat16",
            # same deferred harness as the device-resident epoch bench:
            # at 4 steps/epoch a synchronous per-epoch fetch would sit
            # on the critical path of every epoch
            epoch_sync="deferred",
            name="ImageNetResidentBench",
        )
        iwf.initialize(seed=11)  # ships the 256^2 pool to HBM once
        iwf.run_epoch()  # compile + warmup
        iwf.sync_epoch()
        t0 = time.time()
        n_im_epochs = 12
        for _ in range(n_im_epochs):
            iwf.run_epoch()
        iwf.sync_epoch()
        rate = n_imnet * n_im_epochs / (time.time() - t0)
    finally:
        shutil.rmtree(pack_dir, ignore_errors=True)
    print(
        f"epoch bench (HBM-resident imagenet, on-device crops): "
        f"{rate:.0f} img/s",
        file=sys.stderr,
    )
    epoch_rate = ctx.get("epoch_images_per_sec", 0.0)
    return [
        {
            "metric": "imagenet_resident_images_per_sec",
            "value": round(rate, 2),
            "unit": "images/sec",
            "imagenet_resident_vs_device_resident": round(
                rate / epoch_rate, 4
            ) if epoch_rate else 0.0,
        }
    ]


@_section("mnist")
def _sec_mnist(ctx):
    # secondary metric (BASELINE.json): MNIST MLP step latency, plus the
    # dispatch-bound production epoch in scan vs stepwise dispatch
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from znicz_tpu.core.config import root
    from znicz_tpu.loader.fullbatch import FullBatchLoader
    from znicz_tpu.models import mnist as mnist_model
    from znicz_tpu.workflow import StandardWorkflow

    root.mnist.loader.update(
        {"minibatch_size": 100, "n_train": 100, "n_test": 0,
         "validation_ratio": 0.0}
    )
    mwf = mnist_model.build_workflow()
    mwf.initialize(seed=1234)
    mmb = next(iter(mwf.loader.batches("train")))
    mx, my, mmask = (
        jnp.asarray(mmb.data), jnp.asarray(mmb.labels), jnp.asarray(mmb.mask)
    )

    # Device-side measurement: N steps inside ONE compiled lax.fori_loop,
    # so per-step host dispatch and sync overhead amortize to zero and
    # the quotient is pure device step time (sub-ms steps would
    # otherwise drown in host noise).
    step_fn = mwf.train_step_fn
    N_INNER = 1000

    @jax.jit
    def mnist_many_steps(state):
        def body(_, s):
            s2, _m = step_fn(s, mx, my, mmask, 1.0, mwf._ctx)
            return s2
        return lax.fori_loop(0, N_INNER, body, state)

    mstate = mnist_many_steps(mwf.state)  # compile + warmup
    _sync(mstate.params[0]["weights"])

    def mnist_timed():
        nonlocal mstate
        t0 = time.time()
        mstate = mnist_many_steps(mstate)
        _sync(mstate.params[0]["weights"])
        return time.time() - t0

    # host noise is additive-positive: discard the first post-warmup rep
    # (it absorbs still-queued async work) and min over the rest
    mnist_timed()
    mnist_step_ms = min(mnist_timed() for _ in range(4)) / N_INNER * 1000

    # dispatch-bound regime: a small-model PRODUCTION epoch (run_epoch,
    # 100 steps).  The scanned dispatch (one lax.scan per split) removes
    # the per-step host round trip that dominates sub-ms steps; the
    # stepwise number is reported alongside as the contrast.
    gen2 = np.random.default_rng(1)
    m_imgs = gen2.integers(0, 256, (12800, 28, 28, 1), dtype=np.uint8)
    m_labels = gen2.integers(0, 10, 12800).astype(np.int32)
    ctx["mnist_imgs"] = m_imgs

    def mnist_epoch_rate(dispatch: str) -> float:
        ld = FullBatchLoader(
            {"train": m_imgs}, {"train": m_labels}, minibatch_size=128,
            normalization="range",
            normalization_kwargs={"scale": 255.0, "shift": -0.5},
            device_resident=True,
        )
        ewf = StandardWorkflow(
            ld,
            [{"type": "all2all_tanh", "->": {"output_sample_shape": 256}},
             {"type": "softmax", "->": {"output_sample_shape": 10}}],
            decision_config={"max_epochs": 10000},
            default_hyper={"learning_rate": 0.1, "gradient_moment": 0.9},
            epoch_dispatch=dispatch,
        )
        ewf.initialize(seed=3)
        ewf.run_epoch()  # compile + warmup
        ewf.sync_epoch()  # the window opens on a parked prefetch producer
        t0 = time.time()
        for _ in range(3):
            ewf.run_epoch()
        ewf.sync_epoch()
        return 3 * len(m_imgs) / (time.time() - t0)

    mnist_epoch_scan = mnist_epoch_rate("scan")
    mnist_epoch_step = mnist_epoch_rate("step")
    print(
        f"mnist epoch (100 steps): scan {mnist_epoch_scan:.0f} img/s vs "
        f"stepwise {mnist_epoch_step:.0f} img/s",
        file=sys.stderr,
    )
    return [
        {
            "metric": "mnist_mlp_step_ms",
            "value": round(mnist_step_ms, 3),
            "unit": "ms",
            # min-of-4 after a discarded rep: a single-shot reading's
            # first measurement absorbs queued async work
            "mnist_step_method": "fori_loop_1000_min4_discard1",
            "mnist_epoch_scan_images_per_sec": round(mnist_epoch_scan, 1),
            "mnist_epoch_step_images_per_sec": round(mnist_epoch_step, 1),
        }
    ]


@_section("mnist_stream")
def _sec_mnist_stream(ctx):
    # streaming-input training: u8 minibatches cross host->device every
    # step (stepwise dispatch + the prefetch thread) — the regime of
    # ROADMAP's 100x gap.  Beyond the throughput number, this section
    # carries the PIPELINE ATTRIBUTION verdict (where each step's wall
    # went: compute / prefetch-wait / H2D / other) — the measurement the
    # streaming-rebuild rung is judged with, identical to what
    # tools/znicz-doctor prints from this run's metrics.prom.
    import numpy as np

    from znicz_tpu.loader.fullbatch import FullBatchLoader
    from znicz_tpu.observability import PipelineAttribution
    from znicz_tpu.observability import pipeline as pipeline_obs
    from znicz_tpu.workflow import StandardWorkflow

    m_imgs = ctx.get("mnist_imgs")
    if m_imgs is None:
        gen = np.random.default_rng(1)
        m_imgs = gen.integers(0, 256, (12800, 28, 28, 1), dtype=np.uint8)
    m_labels = (
        np.random.default_rng(2).integers(0, 10, len(m_imgs)).astype(np.int32)
    )
    ld = FullBatchLoader(
        {"train": m_imgs},
        {"train": m_labels},
        minibatch_size=128,
        normalization="range",
        normalization_kwargs={"scale": 255.0, "shift": -0.5},
        device_convert=True,
        device_resident=False,
    )
    swf = StandardWorkflow(
        ld,
        [{"type": "all2all_tanh", "->": {"output_sample_shape": 256}},
         {"type": "softmax", "->": {"output_sample_shape": 10}}],
        decision_config={"max_epochs": 10000},
        default_hyper={"learning_rate": 0.1, "gradient_moment": 0.9},
        epoch_dispatch="step",
    )
    swf.initialize(seed=3)
    swf.run_epoch()  # compile + warmup
    # steady-state attribution window: exclude the compile epoch's
    # stall from the fractions the record reports.  It opens and closes
    # on a parked prefetch producer (sync_epoch), so its counts hold no
    # part of a run-ahead
    swf.sync_epoch()
    pipeline_obs.reset_window()
    n_ep = 2
    t0 = time.time()
    for _ in range(n_ep):
        swf.run_epoch()
    swf.sync_epoch()
    stream_rate = n_ep * len(m_imgs) / (time.time() - t0)
    att = PipelineAttribution.from_registry().attribution()
    fr = att.get("fractions", {})
    print(
        f"mnist stream: {stream_rate:.0f} img/s; {att.get('verdict')} "
        f"(compute {fr.get('compute', 0):.2f}, prefetch-wait "
        f"{fr.get('prefetch_wait', 0):.2f}, h2d {fr.get('h2d', 0):.2f}, "
        f"other {fr.get('other', 0):.2f}); "
        f"H2D {(att.get('h2d_bytes_per_second') or 0) / 1e6:.1f} MB/s",
        file=sys.stderr,
    )
    return [
        {
            "metric": "mnist_stream_images_per_sec",
            "value": round(stream_rate, 1),
            "unit": "images/sec",
            # top-level numerics: znicz-bench-diff lifts these into the
            # round diff (*_bound_frac lower-better, *_bytes_per_second
            # higher-better)
            "train_input_bound_frac": float(
                att.get("input_bound_frac", 0.0)
            ),
            "train_h2d_bytes_per_second": float(
                att.get("h2d_bytes_per_second") or 0.0
            ),
            # the full self-describing attribution record ({"type":
            # "pipeline"} — skipped by metric-family walkers, safe
            # through the aggregator round trip like the programs entry)
            "pipeline": att,
        }
    ]


@_section("som")
def _sec_som(ctx):
    # SOM on the device-resident scan path (VERDICT r3 #1: the wiring of
    # device_preproc through every workflow family makes the
    # HBM-resident epoch available to non-backprop trainers too)
    import numpy as np

    from znicz_tpu.loader.fullbatch import FullBatchLoader
    from znicz_tpu.workflow import KohonenWorkflow

    m_imgs = ctx.get("mnist_imgs")
    if m_imgs is None:
        m_imgs = np.random.default_rng(1).integers(
            0, 256, (12800, 28, 28, 1), dtype=np.uint8
        )
    som_loader = FullBatchLoader(
        {"train": m_imgs}, minibatch_size=128,
        normalization="range",
        normalization_kwargs={"scale": 255.0, "shift": -0.5},
        device_resident=True,
    )
    som_wf = KohonenWorkflow(
        som_loader, sx=8, sy=8, total_epochs=10000,
        epoch_sync="deferred",
    )
    som_wf.initialize(seed=5)
    assert som_wf._use_epoch_scan()
    som_wf.run_epoch()  # compile + warmup
    som_wf.sync_epoch()
    t0 = time.time()
    for _ in range(3):
        som_wf.run_epoch()
    som_wf.sync_epoch()
    rate = 3 * len(m_imgs) / (time.time() - t0)
    print(
        f"SOM epoch (device-resident scan): {rate:.0f} img/s",
        file=sys.stderr,
    )
    return [
        {
            "metric": "som_epoch_images_per_sec",
            "value": round(rate, 1),
            "unit": "images/sec",
        }
    ]


# ---------------------------------------------------------------------------
# transformer LM sections.  Fixed configs shared across them:

LM_T = 2048
LM = dict(vocab=8192, d_model=256, n_layers=8, n_heads=8)
LM_B = 8
# mid config (~50M matmul params): shows MFU scaling with model size —
# d=256 matmuls are too small to tile the v5e MXU well; tokens/s is FLAT
# from B=8 to B=32 (step time scales with B — every extra row costs
# proportional time), so the small model is geometry/utilization-bound,
# not framework-bound
LM_MID = dict(vocab=8192, d_model=512, n_layers=12, n_heads=8)
LM_MID_B = 16
LM_SERVE_LENS = (16, 40, 64, 120)  # padded to 32 / 64 / 64 / 128
LM_SERVE_NEW = 64
# block 32: at the mid config the fatter prefill chunk/window halves
# host dispatches for the same pool memory
LM_SERVE_PAGED_BLOCK = 32
# shared-system-prompt stream for the prefix-cache bench: 160 tokens =
# 5 full blocks of 32, cached once and mapped by every later request
LM_PREFIX_SYS = 160
# repeat-heavy mixed stream for the speculative-decoding bench: tiled
# motifs whose GREEDY CONTINUATIONS this seed's mid-config LM locks
# into near-periodic runs (measured offline — prompt repetition alone
# is not enough, the drafter must predict what the model actually
# emits).  Mixed prompt lengths 16/40/64/120 like the other serve
# streams, weighted toward the long prompts that anchor the attractor.
LM_SPEC_STREAM = (
    ((2765, 2796, 6653, 2317), 120),
    ((3347, 4349, 4741), 120),
    ((4069, 5480, 3836), 120),
    ((123, 1175, 3860), 16),
    ((1359, 63), 40),
    ((1805, 2090, 1511, 2733), 16),
    ((4069, 5480, 3836), 64),
    ((2765, 2796, 6653, 2317), 64),
)
LM_SPEC_B = 8  # decode-bound regime: spec trades FLOPs for steps
LM_SPEC_K = 7  # up to 7 drafts/row/tick -> verify widths 2/4/8


def _lm_cleanup():
    import gc

    import jax

    # compiled executables pin HBM; with many LM variants in one process
    # the accumulation OOMed tail sections in r5 trials (each fine in
    # isolation) — every LM section drops its caches on the way out
    jax.clear_caches()
    gc.collect()


def _lm_train_flops_per_token(cfg) -> float:
    # matmul params (QKV+O, FFN, head — embed/pos are gathers/adds) x 2,
    # plus CAUSAL attention scores+weighted-sum 2*T*D per layer per
    # token (avg attended length T/2; the flash kernel skips the
    # entirely-masked blocks, so counting the full bidirectional 4*T*D
    # would inflate MFU ~1.2x at the mid config — the r4 numbers did).
    # Training ~ 3x forward (fwd + input-grad + weight-grad); remat
    # recomputes fwd (~4x) but MFU uses the remat-off run.  Convention
    # reported as lm_flops_convention.
    d, L, v = cfg["d_model"], cfg["n_layers"], cfg["vocab"]
    d_ff = cfg.get("d_ff") or 4 * d
    p_mat = L * (4 * d * d + 2 * d * d_ff) + d * v
    return 3.0 * (2.0 * p_mat + 2.0 * L * LM_T * d)


def _lm_tokens(rows):
    import numpy as np

    return np.random.default_rng(6).integers(
        0, 8192, (rows, LM_T)
    ).astype(np.int32)


def _lm_rate(cfg, b, attention, remat, tokens=None, extra=None) -> float:
    import jax
    import jax.numpy as jnp
    from jax import lax

    from znicz_tpu.core import prng
    from znicz_tpu.loader.fullbatch import FullBatchLoader
    from znicz_tpu.workflow.transformer import TransformerLMWorkflow

    tokens = _lm_tokens(2 * b) if tokens is None else tokens
    t_len = tokens.shape[1]
    prng.seed_all(99)
    ld = FullBatchLoader(
        {"train": tokens[: 2 * b].copy()}, minibatch_size=b
    )
    lwf = TransformerLMWorkflow(
        ld, max_epochs=1, attention=attention, remat=remat,
        **cfg, **(extra or {}),
    )
    lwf.initialize(seed=99)
    lx = jnp.asarray(tokens[:b])
    ly = jnp.zeros((b,), jnp.int32)
    lmask = jnp.ones((b,), jnp.float32)
    lstep = lwf.train_step_fn
    n_inner = 20

    @jax.jit
    def lm_many(state):
        def body(_, s):
            s2, _m = lstep(s, lx, ly, lmask, 1.0, lwf._ctx)
            return s2
        return lax.fori_loop(0, n_inner, body, state)

    st = lm_many(lwf.state)  # compile + warmup
    _sync(st.params[0]["embed"])

    def timed():
        nonlocal st
        t0 = time.time()
        st = lm_many(st)
        _sync(st.params[0]["embed"])
        return time.time() - t0

    dt = min(timed() for _ in range(3)) / n_inner
    return b * t_len / dt


def _lm_rate_safe(cfg, b, attention, remat, tokens=None, extra=None) -> float:
    # a failed variant fails its section (no 0.0 under a device metric's
    # name); the cleanup still runs so later sections keep their HBM
    try:
        return _lm_rate(cfg, b, attention, remat, tokens=tokens,
                        extra=extra)
    finally:
        _lm_cleanup()


@_section("lm_train")
def _sec_lm_train(ctx):
    # the flagship beyond-parity model needs a driver-visible number
    # (VERDICT r3 #2).  Fixed ~11M-param GPT-small, T=2048, bf16-on-MXU
    # (jax default matmul precision), single chip.  Measured exactly
    # like the MNIST step: N steps inside ONE compiled fori_loop, min
    # over repeats, block_until_ready sync.
    import numpy as np

    peak = _peak_flops()
    lm_flash = _lm_rate_safe(LM, LM_B, "flash", remat=False)
    lm_dense = _lm_rate_safe(LM, LM_B, "dot", remat=False)
    lm_flash_remat = _lm_rate_safe(LM, LM_B, "flash", remat=True)
    lm_mfu = lm_flash * _lm_train_flops_per_token(LM) / peak
    mid_b = LM_MID_B
    lm_mid = _lm_rate_safe(LM_MID, mid_b, "flash", remat=False)
    lm_mid_mfu = lm_mid * _lm_train_flops_per_token(LM_MID) / peak
    ctx["lm_mid_tokens_per_sec"] = lm_mid

    # hd=128 variant (same d=512 tower, 4 heads x 128): tests the r4
    # hypothesis that QK^T at head_dim 64 half-fills the MXU's 128-lane
    # contraction dim.  Same matmul params, same counted FLOPs.
    LM_HD128 = dict(LM_MID, n_heads=4)
    lm_hd128 = _lm_rate_safe(LM_HD128, mid_b, "flash", remat=False)
    lm_hd128_mfu = lm_hd128 * _lm_train_flops_per_token(LM_HD128) / peak

    # bf16 attention (q/k/v on the MXU in bf16, f32 accumulation): the
    # r5 kernel keeps input dtype — standalone fwd+full-bwd 12.7 -> 10.7
    # ms (hd64) / 6.0 -> 4.3 ms (hd128)
    bf16 = dict(attention_dtype="bf16")
    lm_mid_bf16 = _lm_rate_safe(
        LM_MID, mid_b, "flash", remat=False, extra=bf16
    )
    lm_hd128_bf16 = _lm_rate_safe(
        LM_HD128, mid_b, "flash", remat=False, extra=bf16
    )
    lm_hd128_bf16_mfu = (
        lm_hd128_bf16 * _lm_train_flops_per_token(LM_HD128) / peak
    )

    # MoE perf at matched ACTIVE FLOPs (VERDICT r4 weak #3): E=8 experts
    # of d_ff=1024 at top_k=2 activate exactly the dense tower's
    # d_ff=2048-worth of FFN FLOPs per token, so tokens/s is directly
    # comparable to lm_mid.  Dense dispatch runs all 8 experts (4x the
    # active FFN FLOPs — the "trades k/E of the FLOPs" cost made
    # visible); capacity dispatch computes only the routed tokens.
    LM_MOE = dict(LM_MID, d_ff=1024)
    moe_kw = dict(moe_experts=8, moe_top_k=2)
    lm_moe_dense = _lm_rate_safe(
        LM_MOE, mid_b, "flash", remat=False,
        extra=dict(moe_kw, moe_dispatch="dense"),
    )
    lm_moe_capacity = _lm_rate_safe(
        LM_MOE, mid_b, "flash", remat=False,
        extra=dict(moe_kw, moe_dispatch="capacity"),
    )

    # long context: flash (O(T*D) memory) + remat train the mid model at
    # 8x the headline sequence length on ONE chip — dense attention OOMs
    # at T=2048 already.  T=16384, B=2 (32k tokens/step, same as mid).
    LM_LONG_T, LM_LONG_B = 16384, 2
    lm_long_tokens = np.random.default_rng(8).integers(
        0, 8192, (2 * LM_LONG_B, LM_LONG_T)
    ).astype(np.int32)
    lm_long = _lm_rate_safe(
        LM_MID, LM_LONG_B, "flash", remat=True, tokens=lm_long_tokens
    )
    print(
        f"LM GPT-small T={LM_T}: flash {lm_flash:.0f} tok/s "
        f"(causal MFU {lm_mfu:.3f}), dense {lm_dense:.0f}, "
        f"flash+remat {lm_flash_remat:.0f}; "
        f"mid 512dx12L: {lm_mid:.0f} tok/s (MFU {lm_mid_mfu:.3f}); "
        f"hd128 4Hx128: {lm_hd128:.0f} tok/s (MFU {lm_hd128_mfu:.3f}); "
        f"bf16-attn mid {lm_mid_bf16:.0f} / hd128 {lm_hd128_bf16:.0f} "
        f"tok/s (MFU {lm_hd128_bf16_mfu:.3f}); "
        f"moe E=8 k=2 dense {lm_moe_dense:.0f} / capacity "
        f"{lm_moe_capacity:.0f} tok/s; long T={LM_LONG_T}: "
        f"{lm_long:.0f} tok/s",
        file=sys.stderr,
    )
    return [
        {
            "metric": "lm_tokens_per_sec",
            "value": round(lm_flash, 1),
            "unit": "tokens/sec",
            "lm_config": (
                f"GPT-small {LM['d_model']}d x {LM['n_layers']}L x "
                f"{LM['n_heads']}H, vocab {LM['vocab']}, T={LM_T}, "
                f"B={LM_B}, bf16-on-MXU"
            ),
            "lm_mfu": round(lm_mfu, 4),
            "lm_flash_vs_dense": round(
                lm_flash / lm_dense if lm_dense else 0.0, 4
            ),
            "lm_remat_vs_no_remat": round(
                lm_flash_remat / lm_flash if lm_flash else 0.0, 4
            ),
            "lm_mid_config": (
                f"{LM_MID['d_model']}d x {LM_MID['n_layers']}L x "
                f"{LM_MID['n_heads']}H, vocab {LM_MID['vocab']}, "
                f"T={LM_T}, B={mid_b}"
            ),
            "lm_mid_tokens_per_sec": round(lm_mid, 1),
            "lm_mid_mfu": round(lm_mid_mfu, 4),
            # MFU accounting counts CAUSAL attention (2*L*T*D per token
            # — avg attended length T/2, matching what the flash kernel
            # actually computes), not bidirectional
            "lm_flops_convention": "causal_attention_2LTD",
            "lm_hd128_config": (
                f"{LM_HD128['d_model']}d x {LM_HD128['n_layers']}L x "
                f"4H(hd=128), T={LM_T}, B={mid_b}"
            ),
            "lm_hd128_tokens_per_sec": round(lm_hd128, 1),
            "lm_hd128_mfu": round(lm_hd128_mfu, 4),
            "lm_hd128_vs_mid": round(
                lm_hd128 / lm_mid if lm_mid else 0.0, 4
            ),
            "lm_mid_bf16_attn_tokens_per_sec": round(lm_mid_bf16, 1),
            "lm_hd128_bf16_attn_tokens_per_sec": round(lm_hd128_bf16, 1),
            "lm_hd128_bf16_attn_mfu": round(lm_hd128_bf16_mfu, 4),
            "lm_best_vs_r4_mid": round(
                max(lm_hd128_bf16, lm_hd128, lm_mid_bf16, lm_mid)
                / 134730.3,
                4,
            ),
            "lm_moe_config": (
                "mid tower, E=8 experts d_ff=1024 top_k=2 "
                "(active FFN FLOPs == dense d_ff=2048)"
            ),
            "lm_moe_dense_tokens_per_sec": round(lm_moe_dense, 1),
            "lm_moe_capacity_tokens_per_sec": round(lm_moe_capacity, 1),
            "lm_moe_dense_vs_dense_ffn": round(
                lm_moe_dense / lm_mid if lm_mid else 0.0, 4
            ),
            "lm_moe_capacity_vs_dense_ffn": round(
                lm_moe_capacity / lm_mid if lm_mid else 0.0, 4
            ),
            "lm_long_context": (
                f"mid config at T={LM_LONG_T}, B={LM_LONG_B}, "
                "flash+remat (dense OOMs at T=2048 already)"
            ),
            "lm_long_tokens_per_sec": round(lm_long, 1),
        }
    ]


@_section("lm_decode")
def _sec_lm_decode(ctx):
    # KV-cache decode (VERDICT r4 weak #2): greedy generation on the mid
    # config — prefill 64-token prompts, decode 256 new tokens/row in
    # ONE compiled program; rate counts generated tokens only.
    import jax.numpy as jnp

    from znicz_tpu.core import prng
    from znicz_tpu.workflow.generate import generate as lm_generate
    from znicz_tpu.workflow.transformer import init_lm_params

    cfg, b, prompt_len, new_tokens = LM_MID, LM_MID_B, 64, 256
    try:
        prng.seed_all(97)
        params = init_lm_params(
            cfg["vocab"], cfg["d_model"], cfg["n_layers"], cfg["n_heads"],
            max_seq=prompt_len + new_tokens,
        )
        prompt = jnp.asarray(
            _lm_tokens(b)[:, :prompt_len] % cfg["vocab"], jnp.int32
        )
        kw = dict(n_heads=cfg["n_heads"], max_new_tokens=new_tokens)
        out = lm_generate(params, prompt, **kw)  # compile + warmup
        _sync(out.astype(jnp.float32))

        def timed():
            t0 = time.time()
            o = lm_generate(params, prompt, **kw)
            _sync(o.astype(jnp.float32))
            return time.time() - t0

        dt = min(timed() for _ in range(3))
        rate = b * new_tokens / dt
    finally:
        _lm_cleanup()
    return [
        {
            "metric": "lm_decode_tokens_per_sec",
            "value": round(rate, 1),
            "unit": "tokens/sec",
            "lm_decode_config": (
                "mid config, greedy KV-cache decode: prompt 64, "
                f"256 new tokens, B={b}, one lax.scan"
            ),
        }
    ]


def _lm_serve_params():
    from znicz_tpu.core import prng
    from znicz_tpu.workflow.transformer import init_lm_params

    cfg = LM_MID
    prng.seed_all(95)
    return init_lm_params(
        cfg["vocab"], cfg["d_model"], cfg["n_layers"], cfg["n_heads"],
        max_seq=256,
    )


@_section("lm_serve_paged")
def _sec_lm_serve_paged(ctx):
    # PAGED serving (ISSUE 4): a mixed-prompt-length stream through the
    # block-pool engine, pool sized to a full t_max window a slot (B
    # slots x t_max tokens), plus a max-sustained-concurrency probe:
    # 2x the slots against that same pool with short requests — a
    # [B, t_max] reservation would cap at B rows in this memory; the
    # paged pool packs them by blocks actually used (peak_active is the
    # measured answer, preemptions how often pressure forced an
    # eviction).  Prefix cache OFF here: the stream shares no prefixes.
    import numpy as np

    from znicz_tpu.services.engine import PagedDecodeEngine

    cfg, b = LM_MID, LM_MID_B
    try:
        params = _lm_serve_params()
        reqs = np.random.default_rng(12)
        block = LM_SERVE_PAGED_BLOCK
        n_blocks = b * (256 // block) + 1  # a full window a slot + null block

        def make_engine(slots):
            return PagedDecodeEngine(
                params, n_heads=cfg["n_heads"], eos_id=0,
                batch_size=slots, admit_every=8, max_seq=256,
                block_size=block, n_blocks=n_blocks, prefix_cache=False,
            )

        def stream(eng, n):
            for j in range(n):
                length = LM_SERVE_LENS[j % len(LM_SERVE_LENS)]
                eng.submit(
                    reqs.integers(1, cfg["vocab"], (length,)).astype(
                        np.int32
                    ),
                    max_new_tokens=LM_SERVE_NEW,
                )
            return eng.run()

        stream(make_engine(b), len(LM_SERVE_LENS))  # warm both programs
        eng = make_engine(b)  # fresh engine rides the warm jit cache
        t0 = time.time()
        comps = stream(eng, 4 * b)
        wall = time.time() - t0
        toks = sum(c.n_new for c in comps)
        rate, st = toks / wall, eng.stats()
        # concurrency probe: short requests (16-token prompts, 16-token
        # budgets = 2 blocks each) through 2x slots over the same pool
        probe = make_engine(2 * b)
        for _ in range(4 * b):
            probe.submit(
                reqs.integers(1, cfg["vocab"], (16,)).astype(np.int32),
                max_new_tokens=16,
            )
        probe.run()
        probe_st = probe.stats()
    finally:
        _lm_cleanup()
    print(
        f"LM serving PAGED (block {LM_SERVE_PAGED_BLOCK}, mixed prompts "
        f"{LM_SERVE_LENS}): {rate:.0f} tok/s "
        f"({st.get('n_programs', 0)} programs, "
        f"{st.get('preemptions', 0)} preemptions); "
        f"concurrency probe peak {probe_st.get('peak_active', 0)} "
        f"rows (a [B, t_max] reservation caps at {b} in the same memory)",
        file=sys.stderr,
    )
    return [
        {
            "metric": "lm_serve_paged_tokens_per_sec",
            "value": round(rate, 1),
            "unit": "tokens/sec",
            "lm_serve_paged_config": (
                f"mid config paged engine: B={b} slots, "
                f"block {LM_SERVE_PAGED_BLOCK}, pool == a full "
                f"window a slot ({b}x256 tokens), mixed prompts "
                f"{LM_SERVE_LENS}, budget {LM_SERVE_NEW}; probe: "
                f"2x slots, 16+16-token requests, same pool"
            ),
            "lm_serve_paged_compiles": st.get("n_programs", 0),
            "lm_serve_paged_preemptions": st.get("preemptions", 0),
            "lm_serve_paged_max_concurrency": probe_st.get(
                "peak_active", 0
            ),
            "lm_serve_paged_latency_ms": {
                k: round(v, 1)
                for k, v in st.get("latency", {}).items()
            },
        }
    ]


@_section("lm_serve_prefix")
def _sec_lm_serve_prefix(ctx):
    # PREFIX-CACHE serving (ISSUE 5): a shared-system-prompt stream
    # (the production-dominant shape: one 160-token system prefix, a
    # short per-user tail) through the paged engine with the prefix
    # cache ON vs the identical engine with it OFF.  The warm engine
    # maps the system prompt's 5 blocks out of cache at every
    # admission and chunk-prefills only the tail, so TTFT collapses to
    # the tail — lm_serve_prefix_ttft_vs_cold is the measured ratio
    # (lower is better; <1 means the cache pays).
    import numpy as np

    from znicz_tpu.services.engine import PagedDecodeEngine

    cfg, b = LM_MID, LM_MID_B
    try:
        from znicz_tpu.core import prng
        from znicz_tpu.workflow.transformer import init_lm_params

        t_max = 384  # 160-token system prompt + tail + budget
        prng.seed_all(95)
        params = init_lm_params(
            cfg["vocab"], cfg["d_model"], cfg["n_layers"],
            cfg["n_heads"], max_seq=t_max,
        )
        block = LM_SERVE_PAGED_BLOCK
        n_blocks = b * (t_max // block) + 1
        gen = np.random.default_rng(14)
        sys_prompt = gen.integers(
            1, cfg["vocab"], (LM_PREFIX_SYS,)
        ).astype(np.int32)

        def make_engine(prefix):
            return PagedDecodeEngine(
                params, n_heads=cfg["n_heads"], eos_id=0,
                batch_size=b, admit_every=8, max_seq=t_max,
                block_size=block, n_blocks=n_blocks,
                prefix_cache=prefix,
            )

        def stream(eng, n, seed=15):
            r = np.random.default_rng(seed)
            for j in range(n):
                tail = r.integers(
                    1, cfg["vocab"], (16 + 8 * (j % 3),)
                ).astype(np.int32)
                eng.submit(
                    np.concatenate([sys_prompt, tail]),
                    max_new_tokens=LM_SERVE_NEW,
                )
            return eng.run()

        def mean_ttft(comps):
            ts = [c.ttft_s for c in comps if c.ttft_s is not None]
            return sum(ts) / max(len(ts), 1)

        stream(make_engine(True), 4)  # warm every program shape
        # WARM: seed the cache with the bare system prompt, then time
        warm = make_engine(True)
        warm.submit(sys_prompt, 1)
        warm.run()
        t0 = time.time()
        warm_comps = stream(warm, 4 * b)
        warm_wall = time.time() - t0
        warm_rate = sum(c.n_new for c in warm_comps) / warm_wall
        warm_st = warm.stats()
        # COLD: identical engine + stream, cache disabled
        cold = make_engine(False)
        cold.submit(sys_prompt, 1)
        cold.run()
        t0 = time.time()
        cold_comps = stream(cold, 4 * b)
        cold_wall = time.time() - t0
        cold_rate = sum(c.n_new for c in cold_comps) / cold_wall
        ttft_vs_cold = (
            mean_ttft(warm_comps) / mean_ttft(cold_comps)
            if mean_ttft(cold_comps)
            else 0.0
        )
    finally:
        _lm_cleanup()
    pstats = warm_st.get("prefix_cache", {})
    print(
        f"LM serving PREFIX (system prompt {LM_PREFIX_SYS} tokens, "
        f"block {LM_SERVE_PAGED_BLOCK}): warm {warm_rate:.0f} vs cold "
        f"{cold_rate:.0f} tok/s; TTFT warm/cold {ttft_vs_cold:.3f}; "
        f"{pstats.get('hits', 0)} block hits, "
        f"{pstats.get('cached_tokens', 0)} cached tokens",
        file=sys.stderr,
    )
    return [
        {
            "metric": "lm_serve_prefix_tokens_per_sec",
            "value": round(warm_rate, 1),
            "unit": "tokens/sec",
            "lm_serve_prefix_config": (
                f"mid config paged engine + prefix cache: B={b} slots, "
                f"block {LM_SERVE_PAGED_BLOCK}, shared "
                f"{LM_PREFIX_SYS}-token system prompt + 16/24/32-token "
                f"tails, budget {LM_SERVE_NEW}; cold twin runs the "
                "same stream with prefix_cache=False"
            ),
            "lm_serve_prefix_ttft_vs_cold": round(ttft_vs_cold, 4),
            "lm_serve_prefix_vs_cold_tokens_per_sec": round(
                warm_rate / cold_rate if cold_rate else 0.0, 4
            ),
            "lm_serve_prefix_block_hits": pstats.get("hits", 0),
            "lm_serve_prefix_cached_tokens": pstats.get(
                "cached_tokens", 0
            ),
            "lm_serve_prefix_evictions": pstats.get("evictions", 0),
            "lm_serve_prefix_cow_splits": pstats.get("cow_splits", 0),
            "lm_serve_prefix_compiles": warm_st.get("n_programs", 0),
        }
    ]


@_section("lm_serve_spec")
def _sec_lm_serve_spec(ctx):
    # SPECULATIVE serving (ISSUE 12): the repeat-heavy mixed stream
    # through a warm paged engine with prompt-lookup drafting + bucketed
    # parallel verify, against the IDENTICAL engine with spec off.
    # Decode is step-bound: the baseline pays one tower pass per token
    # per chunk iteration; the spec engine verifies up to LM_SPEC_K
    # drafts per row in ONE bucketed pass and keeps the longest agreeing
    # prefix (greedy, so token-identical to the baseline — the twin
    # comparison is apples-to-apples by construction).  Prefix cache OFF
    # on both twins so the speedup is speculation's alone.
    # lm_serve_spec_vs_baseline >= 1.0 is the acceptance bar;
    # _acceptance_rate says why (drafts the verifier kept / proposed).
    import numpy as np

    from znicz_tpu.services.engine import PagedDecodeEngine

    cfg = LM_MID
    b = LM_SPEC_B
    try:
        params = _lm_serve_params()
        block = LM_SERVE_PAGED_BLOCK
        n_blocks = b * (256 // block) + 1

        def make_engine(spec_k):
            return PagedDecodeEngine(
                params, n_heads=cfg["n_heads"], eos_id=0, batch_size=b,
                admit_every=8, max_seq=256, block_size=block,
                n_blocks=n_blocks, prefix_cache=False, spec_k=spec_k,
            )

        def stream(eng, n):
            for j in range(n):
                motif, length = LM_SPEC_STREAM[j % len(LM_SPEC_STREAM)]
                m = np.asarray(motif, np.int32)
                eng.submit(
                    np.tile(m, length // m.size + 1)[:length],
                    max_new_tokens=LM_SERVE_NEW,
                )
            return eng.run()

        # warm every program shape on both twins (one compile set,
        # shared jit caches), then time fresh engines
        stream(make_engine(LM_SPEC_K), len(LM_SPEC_STREAM))
        stream(make_engine(0), len(LM_SPEC_STREAM))
        spec = make_engine(LM_SPEC_K)
        t0 = time.time()
        spec_comps = stream(spec, 2 * b)
        spec_wall = time.time() - t0
        spec_rate = sum(c.n_new for c in spec_comps) / spec_wall
        spec_st = spec.stats()
        base = make_engine(0)
        t0 = time.time()
        base_comps = stream(base, 2 * b)
        base_wall = time.time() - t0
        base_rate = sum(c.n_new for c in base_comps) / base_wall
        # greedy spec is token-identical to the baseline: assert it on
        # the bench stream itself (matched by request id — retirement
        # order may differ) so the headline can never be a
        # divergent-output artifact
        golden = all(
            np.array_equal(
                spec.completions[rid].tokens, base.completions[rid].tokens
            )
            for rid in range(2 * b)
        )
        # divergence is a BUG, not a bench datapoint: fail the section
        # loudly (and emit the flag as an int so a 1 -> 0 flip is a
        # diffable regression, not a silently-skipped bool)
        assert golden, "speculative output diverged from the baseline"
        sp = spec_st.get("spec", {})
    finally:
        _lm_cleanup()
    print(
        f"LM serving SPEC (prompt-lookup k={LM_SPEC_K}, repeat-heavy "
        f"stream): {spec_rate:.0f} vs {base_rate:.0f} tok/s baseline "
        f"(x{spec_rate / base_rate if base_rate else 0.0:.2f}); "
        f"acceptance {sp.get('acceptance_rate', 0.0):.2f} "
        f"({sp.get('accepted', 0)}/{sp.get('drafted', 0)} drafts, "
        f"{sp.get('verify_steps', 0)} verifies); golden={golden}",
        file=sys.stderr,
    )
    return [
        {
            "metric": "lm_serve_spec_tokens_per_sec",
            "value": round(spec_rate, 1),
            "unit": "tokens/sec",
            "lm_serve_spec_config": (
                f"mid config paged engine + prompt-lookup speculation: "
                f"B={b} slots, block {LM_SERVE_PAGED_BLOCK}, "
                f"spec_k={LM_SPEC_K} (verify buckets 2/4/8), "
                f"repeat-heavy mixed prompts 16/40/64/120, budget "
                f"{LM_SERVE_NEW}, greedy; baseline twin is the same "
                "engine with spec_k=0, same stream"
            ),
            "lm_serve_spec_vs_baseline": round(
                spec_rate / base_rate if base_rate else 0.0, 4
            ),
            "lm_serve_spec_acceptance_rate": round(
                float(sp.get("acceptance_rate", 0.0)), 4
            ),
            "lm_serve_spec_compiles": spec_st.get("n_programs", 0),
            "lm_serve_spec_baseline_tokens_per_sec": round(base_rate, 1),
            "lm_serve_spec_drafted": sp.get("drafted", 0),
            "lm_serve_spec_accepted": sp.get("accepted", 0),
            "lm_serve_spec_verify_steps": sp.get("verify_steps", 0),
            "lm_serve_spec_golden": int(golden),
        }
    ]


@_section("lm_serve_frontdoor")
def _sec_lm_serve_frontdoor(ctx):
    # FRONT DOOR serving (ISSUE 6): the same mixed-prompt stream
    # replayed through the REAL HTTP surface — concurrent clients POST
    # /generate against a ServingFrontDoor-owned paged engine and read
    # chunked token streams back.  Reported as a SERVICE, not a
    # library: sustained requests/sec over the timed window, host-side
    # TTFT p99 (first streamed token, queue + HTTP included), and the
    # shed/deadline/cancel/restart tallies that say how the admission
    # ladder behaved under the load.
    import http.client
    import threading

    import numpy as np

    from znicz_tpu.services import serve as serve_mod
    from znicz_tpu.services.engine import PagedDecodeEngine
    from znicz_tpu.services.frontdoor import ServingFrontDoor

    cfg, b = LM_MID, LM_MID_B
    n_requests, n_clients = 4 * b, 4
    door = srv = None
    try:
        params = _lm_serve_params()

        def factory():
            return PagedDecodeEngine(
                params, n_heads=cfg["n_heads"], eos_id=0, batch_size=b,
                admit_every=8, max_seq=256,
                block_size=LM_SERVE_PAGED_BLOCK,
            )

        door = ServingFrontDoor(
            factory, max_pending=2 * n_requests,
            default_deadline_s=300.0,
        )
        srv = serve_mod.build_server(directory=".", port=0, frontdoor=door)
        port = srv.server_address[1]
        threading.Thread(target=srv.serve_forever, daemon=True).start()
        reqs = np.random.default_rng(12)
        prompts = [
            reqs.integers(
                1, cfg["vocab"],
                (LM_SERVE_LENS[j % len(LM_SERVE_LENS)],),
            ).astype(np.int32).tolist()
            for j in range(n_requests)
        ]

        def one_request(prompt):
            t_req = time.time()
            conn = http.client.HTTPConnection(
                "127.0.0.1", port, timeout=300
            )
            try:
                conn.request(
                    "POST", "/generate",
                    body=json.dumps(
                        {"prompt": prompt,
                         "max_new_tokens": LM_SERVE_NEW}
                    ),
                )
                resp = conn.getresponse()
                if resp.status != 200:
                    resp.read()
                    return {"status": resp.status}
                out = {"status": 200, "n_new": 0, "ttft_s": None}
                while True:
                    line = resp.readline()
                    if not line:
                        break
                    rec = json.loads(line)
                    if "token" in rec:
                        if out["ttft_s"] is None:
                            out["ttft_s"] = time.time() - t_req
                        out["n_new"] += 1
                    elif rec.get("done"):
                        out["finish_reason"] = rec.get("finish_reason")
                        out["timings"] = rec.get("timings")
                out["latency_s"] = time.time() - t_req
                return out
            finally:
                conn.close()

        one_request(prompts[0])  # warm every program through HTTP
        todo = list(prompts)
        results: list = []
        lock = threading.Lock()

        def client():
            while True:
                with lock:
                    if not todo:
                        return
                    prompt = todo.pop()
                r = one_request(prompt)
                with lock:
                    results.append(r)

        clients = [
            threading.Thread(target=client, daemon=True)
            for _ in range(n_clients)
        ]
        t0 = time.time()
        for c in clients:
            c.start()
        for c in clients:
            c.join(timeout=600)
        wall = time.time() - t0
        ok = [
            r for r in results
            if r.get("status") == 200
            and r.get("finish_reason") in ("eos", "budget")
        ]
        def pctl(sorted_vals, q):
            if not sorted_vals:
                return 0.0
            i = min(
                len(sorted_vals) - 1,
                int(round(q * (len(sorted_vals) - 1))),
            )
            return sorted_vals[i]

        ttfts = sorted(
            r["ttft_s"] for r in ok if r.get("ttft_s") is not None
        )
        ttft_p99 = pctl(ttfts, 0.99)
        ttft_p50 = pctl(ttfts, 0.5)
        # queue age from the done records' timings breakdown (ISSUE 7):
        # how long requests WAITED (front-door pending + engine queue)
        # before any tower work — the admission-ladder health number
        queue_ages = sorted(
            r["timings"]["queue_s"] for r in ok
            if isinstance(r.get("timings"), dict)
            and r["timings"].get("queue_s") is not None
        )
        queue_age_p99 = pctl(queue_ages, 0.99)
        toks = sum(r.get("n_new", 0) for r in results)
        st = door.stats()
    finally:
        if srv is not None and door is not None:
            serve_mod.shutdown_gracefully(srv, door, grace_s=10.0)
        _lm_cleanup()
    print(
        f"LM serving FRONT DOOR ({n_clients} HTTP clients, "
        f"{n_requests} mixed requests): {len(ok) / wall:.2f} req/s, "
        f"{toks / wall:.0f} tok/s, TTFT p99 {1000 * ttft_p99:.0f} ms; "
        f"shed={sum(st['rejected'].values())} "
        f"deadline={st['deadline_exceeded']} "
        f"restarts={st['watchdog_restarts']}",
        file=sys.stderr,
    )
    return [
        {
            "metric": "lm_serve_frontdoor_rps",
            "value": round(len(ok) / wall, 3),
            "unit": "requests/sec",
            "lm_serve_frontdoor_config": (
                f"mid config paged engine behind ServingFrontDoor + "
                f"HTTP: B={b} slots, block {LM_SERVE_PAGED_BLOCK}, "
                f"{n_clients} concurrent clients streaming "
                f"{n_requests} mixed prompts {LM_SERVE_LENS}, budget "
                f"{LM_SERVE_NEW}"
            ),
            "lm_serve_frontdoor_tokens_per_sec": round(toks / wall, 1),
            "lm_serve_frontdoor_ttft_p99_ms": round(1000 * ttft_p99, 1),
            "lm_serve_frontdoor_ttft_p50_ms": round(1000 * ttft_p50, 1),
            "lm_serve_frontdoor_queue_age_p99_ms": round(
                1000 * queue_age_p99, 1
            ),
            "lm_serve_frontdoor_completed": len(ok),
            "lm_serve_frontdoor_rejected": sum(st["rejected"].values()),
            "lm_serve_frontdoor_deadline_exceeded": st[
                "deadline_exceeded"
            ],
            "lm_serve_frontdoor_cancelled": st["cancelled"],
            "lm_serve_frontdoor_watchdog_restarts": st[
                "watchdog_restarts"
            ],
            "lm_serve_frontdoor_compiles": st["engine"].get(
                "n_programs", 0
            ),
        }
    ]


@_section("lm_serve_router")
def _sec_lm_serve_router(ctx):
    # MULTI-REPLICA ROUTING (ISSUE 8): the shared-system-prompt stream
    # of lm_serve_prefix, but MIXED — several prompt FAMILIES, each a
    # 160-token shared prefix with short per-request tails — replayed
    # through the real router HTTP surface over TWO in-process
    # replicas.  Prefix-affinity placement keeps each family on one
    # replica (one cold prefill per family fleet-wide); the
    # round-robin baseline splits every family across both replicas
    # and pays the cold prefill once per replica.  Reported:
    # lm_serve_router_hit_rate (replica-measured prefix-cache hit
    # fraction under affinity routing) and
    # lm_serve_router_ttft_vs_roundrobin (mean client-clock TTFT
    # ratio, affinity/round-robin — below 1.0 means cache-aware
    # placement pays on this stream).
    import http.client
    import threading

    import numpy as np

    from znicz_tpu.cluster import ServingRouter, build_router_server
    from znicz_tpu.core import prng
    from znicz_tpu.services import serve as serve_mod
    from znicz_tpu.services.engine import PagedDecodeEngine
    from znicz_tpu.services.frontdoor import ServingFrontDoor
    from znicz_tpu.workflow.transformer import init_lm_params

    cfg, b = LM_MID, LM_MID_B
    n_replicas, n_families, per_family = 2, 3, 4
    budget = 24
    block = LM_SERVE_PAGED_BLOCK
    t_max = 384
    try:
        prng.seed_all(95)
        params = init_lm_params(
            cfg["vocab"], cfg["d_model"], cfg["n_layers"],
            cfg["n_heads"], max_seq=t_max,
        )
        gen = np.random.default_rng(14)
        families = [
            gen.integers(1, cfg["vocab"], (LM_PREFIX_SYS,)).astype(
                np.int32
            )
            for _ in range(n_families)
        ]
        # interleaved order: family affinity has to survive the other
        # families' traffic between two same-family requests
        prompts = [
            np.concatenate(
                [
                    families[f],
                    gen.integers(1, cfg["vocab"], (16 + 8 * f,)).astype(
                        np.int32
                    ),
                ]
            )
            for j in range(per_family)
            for f in range(n_families)
        ]

        def one_request(port, prompt):
            t_req = time.time()
            conn = http.client.HTTPConnection(
                "127.0.0.1", port, timeout=300
            )
            try:
                conn.request(
                    "POST", "/generate",
                    body=json.dumps(
                        {"prompt": [int(t) for t in prompt],
                         "max_new_tokens": budget}
                    ),
                )
                resp = conn.getresponse()
                if resp.status != 200:
                    resp.read()
                    return {"status": resp.status}
                out = {"status": 200, "n_new": 0, "ttft_s": None}
                while True:
                    line = resp.readline()
                    if not line:
                        break
                    rec = json.loads(line)
                    if "token" in rec:
                        if out["ttft_s"] is None:
                            out["ttft_s"] = time.time() - t_req
                        out["n_new"] += 1
                    elif rec.get("done"):
                        out["router"] = rec.get("router", {})
                return out
            finally:
                conn.close()

        def run_policy(policy):
            # EVERYTHING from the first door on is inside the try: a
            # mid-setup failure must tear down whatever already
            # started (engine threads, bound sockets, the heartbeat)
            # instead of leaking it into the rest of the round
            doors, srvs = [], []
            router = rsrv = None
            try:
                for _ in range(n_replicas):
                    door = ServingFrontDoor(
                        lambda: PagedDecodeEngine(
                            params, n_heads=cfg["n_heads"], eos_id=0,
                            batch_size=b, admit_every=8, max_seq=t_max,
                            block_size=block,
                        ),
                        max_pending=2 * len(prompts),
                    )
                    doors.append(door)
                    srv = serve_mod.build_server(
                        directory=".", port=0, frontdoor=door
                    )
                    srvs.append(srv)
                    threading.Thread(
                        target=srv.serve_forever, daemon=True
                    ).start()
                router = ServingRouter(block_size=block, policy=policy)
                for i, srv in enumerate(srvs):
                    router.register(
                        f"replica-{i}",
                        f"http://127.0.0.1:{srv.server_address[1]}",
                    )
                rsrv = build_router_server(router, port=0)
                threading.Thread(
                    target=rsrv.serve_forever, daemon=True
                ).start()
                port = rsrv.server_address[1]
                # sequential replay: per-request TTFT then measures
                # prefill (cold vs cached), not queueing noise
                t0 = time.time()
                results = [one_request(port, p) for p in prompts]
                wall = time.time() - t0
                ok = [r for r in results if r.get("status") == 200]
                ttfts = [
                    r["ttft_s"] for r in ok
                    if r.get("ttft_s") is not None
                ]
                hits = misses = 0
                for door in doors:
                    pc = door.engine.stats()["prefix_cache"]
                    hits += pc["hits"]
                    misses += pc["misses"]
                stats = router.stats()
                compiles = max(
                    door.engine.stats().get("n_programs", 0)
                    for door in doors
                )
                return {
                    "ok": len(ok),
                    "wall": wall,
                    "tokens": sum(r.get("n_new", 0) for r in ok),
                    "mean_ttft": sum(ttfts) / max(len(ttfts), 1),
                    "hits": hits,
                    "misses": misses,
                    "retries": sum(
                        r.get("router", {}).get("retries", 0)
                        for r in ok
                    ),
                    "replicas_used": len(
                        {
                            r.get("router", {}).get("replica")
                            for r in ok
                        }
                    ),
                    "stats": stats,
                    "compiles": compiles,
                }
            finally:
                for srv in srvs:
                    srv.shutdown()
                    srv.server_close()
                if rsrv is not None:
                    rsrv.shutdown()
                    rsrv.server_close()
                for door in doors:
                    door.close(grace_s=10.0)
                if router is not None:
                    router.close()

        run_policy("prefix_affinity")  # warm every program through HTTP
        aff = run_policy("prefix_affinity")
        rr = run_policy("round_robin")
        hit_rate = aff["hits"] / max(aff["hits"] + aff["misses"], 1)
        rr_hit_rate = rr["hits"] / max(rr["hits"] + rr["misses"], 1)
        ttft_vs_rr = (
            aff["mean_ttft"] / rr["mean_ttft"]
            if rr["mean_ttft"]
            else 0.0
        )
    finally:
        _lm_cleanup()
    print(
        f"LM serving ROUTER ({n_replicas} replicas, {n_families} "
        f"prompt families x {per_family}): affinity hit rate "
        f"{hit_rate:.2f} vs RR {rr_hit_rate:.2f}; TTFT "
        f"affinity/RR {ttft_vs_rr:.3f}; "
        f"{aff['ok']}/{len(prompts)} ok, retries {aff['retries']}",
        file=sys.stderr,
    )
    return [
        {
            "metric": "lm_serve_router_hit_rate",
            "value": round(hit_rate, 4),
            "unit": "fraction",
            "lm_serve_router_config": (
                f"mid config, {n_replicas} in-process paged replicas "
                f"(B={b} slots, block {block}) behind the prefix-"
                f"affinity router; {n_families} families of "
                f"{LM_PREFIX_SYS}-token shared prefixes x "
                f"{per_family} requests, interleaved, budget {budget}; "
                "round-robin twin runs the identical stream on fresh "
                "replicas"
            ),
            "lm_serve_router_ttft_vs_roundrobin": round(ttft_vs_rr, 4),
            "lm_serve_router_roundrobin_hit_rate": round(
                rr_hit_rate, 4
            ),
            "lm_serve_router_tokens_per_sec": round(
                aff["tokens"] / aff["wall"], 1
            ),
            "lm_serve_router_completed": aff["ok"],
            "lm_serve_router_retries": aff["retries"],
            "lm_serve_router_replicas_used": aff["replicas_used"],
            "lm_serve_router_ttft_ms": round(
                1000 * aff["mean_ttft"], 1
            ),
            "lm_serve_router_roundrobin_ttft_ms": round(
                1000 * rr["mean_ttft"], 1
            ),
            "lm_serve_router_compiles": aff["compiles"],
        }
    ]


# ---------------------------------------------------------------------------


def main() -> None:
    """Run every section (or the ``--only <prefix>`` subset) under
    per-section isolation; exit 1 if any section failed — their error
    records (and every other section's metric records) still printed."""
    only = None
    argv = sys.argv[1:]
    if "--only" in argv:
        i = argv.index("--only")
        if i + 1 >= len(argv):
            print("--only needs a metric-prefix argument", file=sys.stderr)
            raise SystemExit(2)
        only = argv[i + 1]
    try:
        _init_backend()
        from znicz_tpu.core import backend

        backend.enable_compile_cache()
    except Exception as e:
        emit(
            {
                "error": type(e).__name__,
                "section": "backend_init",
                "detail": str(e)[:500],
                "metrics_snapshot": _metrics_snapshot(),
            }
        )
        print(f"bench failed: {type(e).__name__}: {e}", file=sys.stderr)
        raise SystemExit(1)
    failed = run_sections(only=only)
    # full telemetry registry behind this run's numbers: phase
    # histograms, serve counters/latency, cache stats.  The compile
    # ledger's headline rides as TOP-LEVEL numeric fields — the
    # driver's "parsed" merge (and znicz-bench-diff's record flatten)
    # only lift top-level numbers, so nesting them under
    # metrics_snapshot would make the compile-count gate inert
    emit(
        {
            "metric": "bench_sections_failed",
            "value": len(failed),
            "failed_sections": failed,
            **_program_headline(),
            "metrics_snapshot": _metrics_snapshot(),
        }
    )
    if failed:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
