"""The Workflow: host control loop around one jit-compiled train step.

Re-founds ``veles/workflow.py``'s event-driven unit DAG (SURVEY.md 3.1) as:

    loader -> [jitted: forward + loss + grad + update + metrics] -> decision
                                                     \\-> snapshotter

The hot loop (Repeater->Loader->forwards->evaluator->GDs of SURVEY.md 3.1) is
ONE XLA program; epoch bookkeeping, stopping, snapshots and services stay in
Python exactly where the reference kept its gate-driven units.  Metric
device->host syncs happen once per epoch, not per minibatch.
"""

from __future__ import annotations

import contextlib
import time
import weakref
from collections import deque
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from znicz_tpu.core import prng
from znicz_tpu.core.logger import Logger
from znicz_tpu.loader.base import TRAIN, Loader
from znicz_tpu.loader.prefetch import CarriedEpochs
from znicz_tpu.nn import evaluator, optimizer
from znicz_tpu.nn.decision import Decision
from znicz_tpu.nn.train_state import TrainState
from znicz_tpu.observability import PhaseTimer
from znicz_tpu.observability import pipeline as pipeline_obs
from znicz_tpu.observability.anomaly import StepAnomalyDetector
from znicz_tpu.utils import faults
from znicz_tpu.utils.profiling import Stopwatch
from znicz_tpu.workflow.model import Model
from znicz_tpu.workflow.recovery import (
    RecoveryPolicy,
    RollbackExhaustedError,
    TrainingPreempted,
)
from znicz_tpu.workflow.snapshotter import (
    SnapshotCorruptError,
    Snapshotter,
    SnapshotWriteError,
    find_latest_valid,
    load_snapshot,
)


class _RollbackSignal(Exception):
    """Internal control flow: an anomaly verdict asked for a rollback.
    Raised at the feed points, caught by :meth:`Workflow.run_epoch`."""

    def __init__(self, reason: str):
        super().__init__(reason)
        self.reason = reason


class _PreemptSignal(Exception):
    """Internal control flow: a requested stop reached a step boundary
    mid-epoch (the in-flight dispatch has drained)."""


def _batch_stager(target_of, put, probe):
    """``(split, minibatch) -> (split, x, y, mask)`` on the device: the
    host's target pick and the ``device_put`` calls for one batch.  Run
    inside the prefetch worker this overlaps the host->device transfer
    with the previous step's compute (device_put is thread-safe and
    async).  ``probe`` (an H2DProbe) owns the stage timing + bytes.
    ``target_of`` is a weak reference to the workflow's
    ``_batch_target`` (None: the target IS the input), so the thread
    that runs this keeps no workflow alive."""

    def stage_item(item):
        split, mb = item
        # autoencoder target IS the input: reuse the device array
        # instead of transferring the batch twice
        y_host = None if target_of is None else target_of()(mb)
        nbytes = (
            getattr(mb.data, "nbytes", 0)
            + getattr(y_host, "nbytes", 0)
            + getattr(mb.mask, "nbytes", 0)
        )
        with probe.measure(nbytes) as transfer:
            x = put(mb.data)
            y = x if y_host is None else put(y_host)
            mask = put(mb.mask)
            # the landing time is taken beside the loop: nobody here
            # waits for the copy
            transfer.watch(x, y, mask)
        return split, x, y, mask

    return stage_item


def _is_additive(name: str) -> bool:
    return not name.startswith("max_")


def _encode_metrics(m: Dict[str, Any], names) -> jnp.ndarray:
    """Metric dict -> epoch-accumulator increments, INSIDE the jitted step.

    Mirrors :class:`znicz_tpu.nn.decision.EpochMetrics` semantics: counts
    add, means add sample-weighted, ``max_*`` metrics combine by maximum.
    """
    n = jnp.asarray(m["n_samples"], jnp.float32)
    vals = []
    for k in names:
        v = jnp.asarray(m[k], jnp.float32)
        if k in ("n_samples", "n_err") or not _is_additive(k):
            vals.append(v)
        else:  # sample-weighted sum; decoded back to a mean at epoch end
            vals.append(v * n)
    return jnp.stack(vals)


def _global_norm(tree) -> jnp.ndarray:
    """Global L2 norm of a pytree (f32 accumulation) — the grad-norm
    half of the per-step anomaly watch vector, computed INSIDE the
    existing jitted step (zero new compiled programs)."""
    s = jnp.zeros((), jnp.float32)
    for leaf in jax.tree_util.tree_leaves(tree):
        d = jnp.asarray(leaf, jnp.float32)
        s = s + jnp.vdot(d, d)
    return jnp.sqrt(s)


def _decode_metrics(acc: np.ndarray, names) -> Dict[str, float]:
    """Accumulator vector -> ONE aggregated metrics dict whose
    ``EpochMetrics.add`` outcome equals adding every minibatch."""
    d = dict(zip(names, np.asarray(acc, np.float64)))
    n = max(float(d.get("n_samples", 0.0)), 1.0)
    return {
        k: float(v)
        if k in ("n_samples", "n_err") or not _is_additive(k)
        else float(v) / n
        for k, v in d.items()
    }


class Workflow(Logger):
    """Owns loader + model + decision + snapshotter; runs training.

    ``loss_function``: "softmax" (cross-entropy on integer labels) or "mse"
    (against ``target`` = "targets" from the loader, or "input" for
    autoencoders) — mirroring EvaluatorSoftmax / EvaluatorMSE.
    """

    def __init__(
        self,
        loader: Loader,
        model: Model,
        *,
        loss_function: str = "softmax",
        target: str = "labels",
        decision: Optional[Decision] = None,
        snapshotter: Optional[Snapshotter] = None,
        lr_policy: Optional[Callable[[float, int], float]] = None,
        parallel=None,
        prefetch_batches: int = 2,
        epoch_dispatch: str = "auto",  # "auto" | "scan" | "step"
        epoch_sync: str = "sync",  # "sync" | "deferred"
        anomaly=True,  # True = default detector; False/None = off
        recovery: Optional[RecoveryPolicy] = None,
        name: str = "workflow",
    ):
        self.loader = loader
        self.model = model
        self.loss_function = loss_function
        self.target = target
        self.decision = decision or Decision(
            metric="n_err" if loss_function == "softmax" else "loss"
        )
        self.snapshotter = snapshotter
        self.lr_policy = lr_policy
        self.parallel = parallel  # DataParallel placement policy, or None
        self.prefetch_batches = prefetch_batches  # 0 disables the loader thread
        if epoch_dispatch not in ("auto", "scan", "step"):
            raise ValueError(
                f"epoch_dispatch={epoch_dispatch!r}: "
                "want 'auto', 'scan' or 'step'"
            )
        self.epoch_dispatch = epoch_dispatch
        if epoch_sync not in ("sync", "deferred"):
            raise ValueError(
                f"epoch_sync={epoch_sync!r}: want 'sync' or 'deferred'"
            )
        self.epoch_sync = epoch_sync
        self._pending_accs = None
        # deferred + save_best: improvement is only known after the lagged
        # fetch, when self.state has advanced one epoch — so each dispatch
        # RETAINS a copy of its epoch's FULL TrainState (params + momentum:
        # ~2x the param bytes in HBM, held one epoch) plus the loader/prng
        # host state, and the best-snapshot writes from that buffer when
        # the lagged verdict resolves.  Interval epochs are known in
        # advance and still flush synchronously before dispatch.
        self._retained = None
        self.services = []  # per-epoch observers: plotters, status, image saver
        self.name = name
        self.state: Optional[TrainState] = None
        self._train_step = None
        self._eval_step = None
        self._eval_conf_step = None
        self._ctx = None
        self._host_step = 0
        # per-phase ledger (SURVEY.md 5.1), re-founded on the telemetry
        # substrate: every phase is a tracer span AND an observation into
        # the registry's znicz_train_phase_seconds histogram — status
        # page, /metrics and bench all read the same series
        self.timer = PhaseTimer(
            "znicz_train_phase_seconds",
            help="training host phase seconds (dispatch, stack, sync)",
            span_prefix="train/",
        )
        # step anomaly flight recorder (docs/OBSERVABILITY.md "Training
        # observability"): fed the per-step loss/grad-norm watch vector
        # the jitted step piggybacks, LAGGED so detection never forces
        # a device sync in the hot loop
        if anomaly is True:
            self.anomaly: Optional[StepAnomalyDetector] = (
                StepAnomalyDetector()
            )
        else:
            self.anomaly = anomaly or None
        # self-healing (docs/TRAINING.md): the recovery policy consumes
        # the detector's verdicts, so it needs the detector on
        if recovery is not None and self.anomaly is None:
            raise ValueError(
                "recovery=... consumes the step anomaly detector's "
                "verdicts; it cannot combine with anomaly=False"
            )
        self.recovery = recovery
        # graceful-stop plumbing: request_stop() (usually from a
        # SIGTERM/SIGINT handler) flips the flag, the loops act on it
        # at the next step boundary
        self._preempt_requested = False
        # when True (enable_emergency_snapshots), each sync-mode epoch
        # retains its START state so a mid-epoch stop/rollback can land
        # on a consistent (state, loader, prng, decision) quadruple
        self._emergency_capture = False
        self._epoch_start = None
        # host->device transfer probe for the streaming batch path; the
        # step-wall histogram it pairs with is observed in the stepwise
        # consumer loop
        self._h2d_probe = pipeline_obs.H2DProbe()
        self._step_wall = pipeline_obs.step_wall_seconds()
        # scanned epochs' watch vectors, drained at the epoch's metric
        # sync ([n_steps, 2] device arrays, copies started at dispatch)
        self._pending_watch: list = []
        # the prefetch producer of stepwise epochs, held from one
        # run_epoch() to the next
        self._feed: Optional[CarriedEpochs] = None

    # ------------------------------------------------------------------
    def _metrics(self, out, y, mask):
        if self.loss_function == "softmax":
            return evaluator.softmax(out, y, mask=mask)
        return evaluator.mse(out, y, mask=mask)

    def _build_steps(self):
        model = self.model

        def loss_fn(params, key, step, x, y, mask):
            rng = jax.random.fold_in(key, step)
            out = model.apply(params, x, train=True, rng=rng)
            m = self._metrics(out, y, mask)
            return m["loss"], m

        def train_step(state: TrainState, x, y, mask, lr_scale):
            grads, metrics = jax.grad(loss_fn, has_aux=True)(
                state.params, state.key, state.step, x, y, mask
            )
            # anomaly-watch input; popped before the epoch accumulator
            metrics = dict(metrics, grad_norm=_global_norm(grads))
            hyper = [
                h._replace(
                    learning_rate=h.learning_rate * lr_scale,
                    learning_rate_bias=(
                        None
                        if h.learning_rate_bias is None
                        else h.learning_rate_bias * lr_scale
                    ),
                )
                for h in model.hyper
            ]
            new_p, new_v = optimizer.update(
                state.params, grads, state.velocity, hyper
            )
            return (
                state._replace(
                    params=new_p, velocity=new_v, step=state.step + 1
                ),
                metrics,
            )

        def eval_step(params, x, y, mask):
            out = model.apply(params, x, train=False)
            return self._metrics(out, y, mask)

        if self.loss_function == "softmax":
            from znicz_tpu.nn import evaluator as _ev

            def eval_conf_step(params, x, y, mask):
                out = model.apply(params, x, train=False)
                return _ev.softmax(out, y, mask=mask, compute_confusion=True)

            names = ["loss", "max_err_y_sum", "n_err", "n_samples"]
        else:
            eval_conf_step = None
            names = ["loss", "max_diff", "n_samples"]
        self._finalize_steps(
            train_step, eval_step, names, eval_conf_step=eval_conf_step,
        )

    def _finalize_steps(
        self,
        train_step,
        eval_step,
        metric_names,
        *,
        eval_conf_step=None,
    ):
        """Jit the raw steps with ON-DEVICE epoch-metric accumulation.

        ``train_step(state, x, y, mask, lr_scale) -> (state, metrics_dict)``
        and ``eval_step(params, x, y, mask) -> metrics_dict`` are wrapped so
        the compiled program also folds each batch's metrics into a single
        f32 accumulator vector.  The epoch then needs exactly ONE small
        device->host fetch per split — O(1) host syncs per epoch on pods.
        No extra XLA programs are created (the combine lives inside the
        step; the init vector is a plain device_put).
        """
        names = sorted(metric_names)
        self._metric_names = names
        is_additive = np.array([_is_additive(k) for k in names])
        self._acc_init_host = np.where(
            is_additive, 0.0, -np.inf
        ).astype(np.float32)
        add_mask = jnp.asarray(is_additive)

        def combine(acc, m):
            vec = _encode_metrics(m, names)
            return jnp.where(add_mask, acc + vec, jnp.maximum(acc, vec))

        # Loader-provided on-device preprocessing (u8 -> f32 affine, mean
        # subtraction, HBM-pool gather) is applied HERE, outside the raw
        # steps, so EVERY workflow — backprop, transformer, SOM, RBM —
        # consumes the loader's device context the same way.  A loader that
        # ships index vectors (device_resident=True) therefore can never
        # leak bare indices into a model as data.  ``ctx`` is the device
        # context pytree: always an explicit jit ARGUMENT so XLA never
        # embeds it in the executable.
        pre = self.loader.device_preproc()
        target_is_input = self.target == "input"

        def prep(x, y, ctx):
            if pre is None:
                return x, y
            x = pre(x, ctx)
            return x, (x if target_is_input else y)  # AE target = preproc'd x

        # every step is TRACED under the placement policy's mesh: an op the
        # partitioner cannot split (a Pallas kernel) finds it there and runs
        # per shard (ops/pallas/lrn.py:_per_shard)
        scope = (
            self.parallel.scope if self.parallel is not None
            else contextlib.nullcontext
        )

        def train_step_full(state, x, y, mask, lr_scale, ctx):
            with scope():
                x, y = prep(x, y, ctx)
                return train_step(state, x, y, mask, lr_scale)

        # trace-time gate: with the detector off the watch output is
        # None, so the norm (and the grad_norm the steps put in their
        # metrics) is dead code XLA eliminates — anomaly=False costs
        # nothing on-device, not just a skipped host read
        watch_enabled = self.anomaly is not None

        def train_acc(state, x, y, mask, lr_scale, acc, ctx):
            """One train step + epoch-accumulator fold + the per-step
            anomaly WATCH vector ``[loss, grad_norm]`` — extra outputs
            of the SAME compiled program, so the flight recorder costs
            zero new XLA programs (tests pin this)."""
            state2, m = train_step_full(state, x, y, mask, lr_scale, ctx)
            m = dict(m)
            gn = m.pop("grad_norm", None)
            if not watch_enabled:
                return state2, combine(acc, m), None
            if gn is None:
                # steps that don't expose grads (SOM, RBM): the update
                # norm ||params' - params|| catches the same
                # pathologies (non-finite, explosion)
                gn = _global_norm(
                    jax.tree_util.tree_map(
                        lambda a, b: b - a, state.params, state2.params
                    )
                )
            watch = jnp.stack(
                [
                    jnp.asarray(m["loss"], jnp.float32),
                    jnp.asarray(gn, jnp.float32),
                ]
            )
            return state2, combine(acc, m), watch

        def eval_acc(params, x, y, mask, acc, ctx):
            with scope():
                x, y = prep(x, y, ctx)
                return combine(acc, eval_step(params, x, y, mask))

        # un-jitted step kept public: benchmarks/tools can embed it in their
        # own compiled programs (e.g. a lax.fori_loop of steps for device-
        # side latency measurement without per-step dispatch overhead); the
        # loader preproc is included so callers pass raw minibatch payloads
        self.train_step_fn = train_step_full
        self._train_step = jax.jit(train_acc, donate_argnums=(0, 5))
        self._eval_step = jax.jit(eval_acc, donate_argnums=(4,))

        # whole-split lax.scan twins: ONE dispatch per split per epoch.
        # For device-resident loaders the per-batch payload is an index
        # vector, so stacking an epoch of them is bytes — and per-step
        # host dispatch drops out of the epoch entirely (see run_epoch's
        # scan path).
        def train_epoch_scan(state, xs, ys, masks, lrs, acc, ctx):
            def body(carry, b):
                st, a = carry
                x, y, mask, lr = b
                st, a, w = train_acc(st, x, y, mask, lr, a, ctx)
                return (st, a), w  # stacked [n_steps, 2] watch

            (state, acc), watches = jax.lax.scan(
                body, (state, acc), (xs, ys, masks, lrs)
            )
            return state, acc, watches

        def eval_epoch_scan(params, xs, ys, masks, acc, ctx):
            def body(a, b):
                x, y, mask = b
                return eval_acc(params, x, y, mask, a, ctx), None

            acc, _ = jax.lax.scan(body, acc, (xs, ys, masks))
            return acc

        self._train_epoch_scan = jax.jit(
            train_epoch_scan, donate_argnums=(0, 5)
        )
        self._eval_epoch_scan = jax.jit(eval_epoch_scan, donate_argnums=(4,))
        if eval_conf_step is not None:

            def eval_conf_acc(params, x, y, mask, acc, conf, ctx):
                with scope():
                    x, y = prep(x, y, ctx)
                    m = eval_conf_step(params, x, y, mask)
                c = m.pop("confusion")
                return combine(acc, m), conf + c

            self._eval_conf_step = jax.jit(
                eval_conf_acc, donate_argnums=(4, 5)
            )
        else:
            self._eval_conf_step = None

    def _put_replicated(self, arr):
        """Host array -> device, replicated over the mesh when a placement
        policy exists (multi-host jitted steps need every non-sharded input
        placed as ONE global array, not a per-process local one)."""
        if self.parallel is not None:
            return self.parallel.put_replicated(arr)
        return jax.device_put(arr)

    def _acc_init(self) -> jax.Array:
        """Fresh epoch accumulator (plain transfer — no compile)."""
        return self._put_replicated(self._acc_init_host.copy())

    # ------------------------------------------------------------------
    def _create_initial_state(self) -> TrainState:
        """Template hook: fresh train state for a non-resume initialize.
        Subclasses with custom param structures override ONLY this."""
        return TrainState.create(
            self.model.params, prng.get("workflow").key()
        )

    def _default_param_rules(self):
        """Template hook: model-aware TP placement rules used when the
        placement policy has ``tp=True`` but no explicit ``param_rules``
        (None keeps DataParallel's size heuristic)."""
        return None

    def initialize(
        self,
        *,
        seed: Optional[int] = None,
        snapshot: Optional[str] = None,
    ) -> None:
        """Create (or resume) the train state and compile the steps."""
        self._park_producer()
        if seed is not None:
            prng.seed_all(seed)
        if snapshot:
            state, host = load_snapshot(snapshot)
            self.state = TrainState(*state)  # host leaves; placed below
            if "decision" in host:
                self.decision.load_state_dict(host["decision"])
            if "loader" in host:
                self.loader.load_state_dict(host["loader"])
            if "prng" in host:
                prng.load_state_dict(host["prng"])
            self.info(
                "resumed from %s at epoch %d", snapshot, self.decision.epoch
            )
        elif self.state is None:
            self.state = self._create_initial_state()
        if self.parallel is not None:
            rules = (
                self._default_param_rules()
                if self.parallel.tp and self.parallel.param_rules is None
                else None
            )
            if rules is not None:
                from znicz_tpu.parallel import DataParallel

                # never mutate the caller's DataParallel (it may be shared)
                self.parallel = DataParallel(
                    self.parallel.mesh,
                    tp=True,
                    tp_min_features=self.parallel.tp_min_features,
                    param_rules=rules,
                )
            self.state = self.parallel.shard_state(self.state)
        elif snapshot:
            # device-place the restored host leaves: a resumed step fed
            # numpy arrays would recompile (placement rides the
            # executable-cache key).  Done HERE, after the (absent)
            # placement-policy branch, so a sharded resume never
            # round-trips the full state through the default device.
            self.state = jax.tree_util.tree_map(
                jax.device_put, self.state
            )
        # multi-host: every process runs this same loop; the loader serves
        # per-process sample shards, snapshot/services write on exactly one
        # process (the reference's master-does-bookkeeping role, SURVEY 3.4)
        from znicz_tpu.parallel import multihost

        self._coordinator = multihost.is_coordinator()
        if multihost.process_count() > 1:
            if self.parallel is None:
                raise ValueError(
                    "multi-host training needs a DataParallel placement "
                    "policy (parallel=...) so batches span the global mesh"
                )
            if self.parallel.n_data % multihost.process_count():
                # the per-process loader contract serves each process a
                # contiguous 1/P block of every global minibatch — only
                # meaningful when its devices own such a block of the axis
                raise ValueError(
                    f"data axis size {self.parallel.n_data} not divisible "
                    f"by process count {multihost.process_count()}; "
                    "multi-host training shards the batch over processes, "
                    "so give every process an equal data-axis share "
                    "(e.g. --mesh data=<n_processes*k>)"
                )
            self.loader.set_process_shard(
                multihost.process_index(), multihost.process_count()
            )
        if self.snapshotter is not None:
            self.snapshotter.writer = self._coordinator
        # host-side mirror of state.step: lr policies read it every minibatch
        # and must not force a device sync in the hot loop
        self._host_step = int(self.state.step)
        # data-axis pool sharding: the loader partitions its dataset over
        # the mesh's data axis (each device holds 1/D of the rows), so the
        # HBM capacity ceiling scales with the mesh instead of one chip
        if self.loader.wants_data_shards:
            if self.parallel is None:
                raise ValueError(
                    "this loader shards its device pool over the data "
                    "axis; pass parallel=DataParallel(mesh)"
                )
            self.loader.set_data_shards(self.parallel.n_data)
        # loader-owned device context (e.g. HBM-resident dataset pool):
        # ONE up-front transfer, threaded through every step as an argument
        self._ctx = self.loader.place_device_context(self.parallel)
        self._build_steps()

    def _batch_target(self, mb):
        """HOST-side target array: the caller's ``put`` does the (sharded)
        device placement — returning a device array here would force a
        blocking readback inside DataParallel.shard_batch every minibatch."""
        if self.target == "labels":
            return mb.labels
        if self.target == "targets":
            return mb.targets
        if self.target == "input":
            # autoencoder: reconstruct the input; evaluator.mse flattens, so
            # the model output only needs to match total feature count
            return mb.data
        raise ValueError(f"unknown target {self.target!r}")

    def _input_state(self) -> Dict[str, Any]:
        """Loader and prng state at the last epoch boundary.  Between
        ``run_epoch()`` calls the prefetch producer is already drawing
        the next epoch, so the loader and its shuffle stream are read
        from the copy the producer took AT the boundary; with no
        producer live they read what the loader reads now."""
        if self._feed is None:
            return {
                "loader": self.loader.state_dict(),
                "prng": prng.state_dict(),
            }
        loader_state, stream_state = self._feed.boundary
        streams = prng.state_dict()
        streams["generators"][self.loader.rand_name] = stream_state
        return {"loader": loader_state, "prng": streams}

    def host_state(self) -> Dict[str, Any]:
        return {
            "decision": self.decision.state_dict(),
            **self._input_state(),
        }

    def _park_producer(self) -> None:
        """Stop the prefetch producer, drop what it ran ahead and put
        the loader back to the last epoch boundary (no-op without one).
        Everything that leaves the ``run_epoch()`` loop, or touches the
        loader from this thread, comes through here first."""
        feed, self._feed = self._feed, None
        if feed is not None:
            feed.park()

    # ------------------------------------------------------------------
    def _use_epoch_scan(self) -> bool:
        """Scan dispatch: whole splits compiled as one lax.scan.  Auto mode
        requires a device-resident loader (per-batch host payloads are bare
        index vectors); under DataParallel the stacked payloads shard on
        their BATCH dim (dim 1) so each scan step sees the same sharded
        batch the stepwise path would."""
        if self.epoch_dispatch == "scan":
            if not getattr(self.loader, "epoch_scan_friendly", False):
                raise ValueError(
                    "epoch_dispatch='scan' needs a scan-friendly loader "
                    "(per-batch host payloads must be small, e.g. "
                    "FullBatchLoader(device_resident=True)); a streaming "
                    "loader would materialize the whole epoch in host RAM"
                )
            return True
        return (
            self.epoch_dispatch == "auto"
            and self._ctx is not None
            and getattr(self.loader, "epoch_scan_friendly", False)
        )

    def _put_stacked(self, arr: np.ndarray) -> jax.Array:
        """Device-place an epoch-stacked [n_steps, B, ...] payload; under
        DataParallel the batch dim (dim 1) shards over the data axis —
        placement policy stays with DataParallel."""
        if self.parallel is None:
            return jnp.asarray(arr)
        return self.parallel.shard_batch(arr, batch_dim=1)

    def _run_epoch_scanned(self) -> Dict[str, jax.Array]:
        """One dispatch per split: stack the epoch's host-side batch
        payloads and scan.  Split order (train, valid, test) matches the
        stepwise path, so results are identical."""
        with self.timer.phase("loader_epoch"):
            per_split: Dict[str, list] = {}
            for split, mb in self.loader.epoch():
                per_split.setdefault(split, []).append(mb)
        accs: Dict[str, jax.Array] = {}
        for split, mbs in per_split.items():
            with self.timer.phase(f"stack/{split}"):
                xs = self._put_stacked(np.stack([mb.data for mb in mbs]))
                ys = (
                    xs
                    if self.target == "input"
                    else self._put_stacked(
                        np.stack([self._batch_target(mb) for mb in mbs])
                    )
                )
                masks = self._put_stacked(np.stack([mb.mask for mb in mbs]))
            with self.timer.phase(f"dispatch/{split}"):
                if split == TRAIN:
                    rec_scale = (
                        self.recovery.lr_scale
                        if self.recovery is not None
                        else 1.0
                    )
                    lrs_host = np.asarray(
                        [
                            (
                                self.lr_policy(1.0, self._host_step + i)
                                if self.lr_policy
                                else 1.0
                            )
                            * rec_scale
                            for i in range(len(mbs))
                        ],
                        np.float32,
                    )
                    lrs = self._put_replicated(lrs_host)
                    start_step = self._host_step
                    self.state, acc, watches = self._train_epoch_scan(
                        self.state, xs, ys, masks, lrs,
                        self._acc_init(), self._ctx,
                    )
                    self._host_step += len(mbs)
                    if self.anomaly is not None:
                        # tiny [n_steps, 2] array; the copy rides behind
                        # the dispatch and is read at the epoch's sync
                        if hasattr(watches, "copy_to_host_async"):
                            watches.copy_to_host_async()
                        self._pending_watch.append((start_step, watches))
                else:
                    acc = self._eval_epoch_scan(
                        self.state.params, xs, ys, masks,
                        self._acc_init(), self._ctx,
                    )
                accs[split] = acc
        return accs

    def run_epoch(self) -> Optional[Dict[str, Any]]:
        """One full epoch over all splits; returns the Decision verdict.

        ``epoch_sync="deferred"``: the device->host metric fetch of epoch N
        overlaps epoch N+1's dispatch, so the per-epoch transport round
        trip drops out of the wall clock.  The returned verdict then lags
        one epoch (None on the very first call); stop decisions stay
        EXACT — when the Decision could possibly stop on the pending
        epoch, it is flushed synchronously before anything new dispatches.

        Self-healing control flow (docs/TRAINING.md): a rollback-worthy
        anomaly verdict aborts the epoch, restores the last good
        snapshot and returns None (the ``run`` loop re-dispatches); a
        requested stop drains the in-flight step, writes an emergency
        snapshot and raises :class:`TrainingPreempted`.
        """
        if self.state is None:
            self.initialize()
        # chaos point: a hard process crash at an epoch boundary (arm
        # with after=k to crash entering epoch k — the supervised
        # auto-resume fixture)
        faults.fire("train.crash")
        if self._preempt_requested:
            self._graceful_exit(mid_epoch=False)
        try:
            return self._run_epoch_inner()
        except _PreemptSignal:
            self._graceful_exit(mid_epoch=True)
        except _RollbackSignal as sig:
            self._execute_rollback(sig.reason)
            return None
        except BaseException:
            self._park_producer()
            raise

    def _run_epoch_inner(self) -> Optional[Dict[str, Any]]:
        deferred = self.epoch_sync == "deferred"
        flushed = None
        # pending must resolve synchronously (BEFORE the next dispatch)
        # when its verdict could stop training, or when it is an interval-
        # snapshot epoch (self.state is still that epoch's right now)
        pending_snapshots = (
            self.snapshotter is not None
            and self.snapshotter.interval
            and (self.decision.epoch + 1) % self.snapshotter.interval == 0
        )
        if (
            deferred
            and self._pending_accs is not None
            and (self.decision.can_stop_next_epoch() or pending_snapshots)
        ):
            accs, self._pending_accs = self._pending_accs, None
            # self.state IS still the pending epoch's (nothing dispatched
            # since), so the retained copy is redundant here — drop it
            self._retained = None
            flushed = self._finish_epoch(accs)
            if flushed["stop"]:
                return flushed  # nothing new dispatched
        if (
            self.recovery is not None or self._emergency_capture
        ) and not deferred:
            # epoch-START retention: the rollback fallback when no
            # snapshot exists yet, and the emergency snapshot's source
            # on a mid-epoch stop — the one point where (state, loader,
            # prng, decision) are mutually consistent.  Fresh buffers
            # (jnp.copy): the train step donates self.state's.
            self._epoch_start = self._retain_epoch_start()
        accs = (
            self._run_epoch_scanned()
            if self._use_epoch_scan()
            else self._run_epoch_stepwise()
        )
        if not deferred:
            return self._finish_epoch(accs)
        for acc in accs.values():  # start the copies behind the dispatch
            if hasattr(acc, "copy_to_host_async"):
                acc.copy_to_host_async()
        prev, self._pending_accs = self._pending_accs, accs
        prev_retained, self._retained = self._retained, (
            self._retain_state()
            if self.snapshotter is not None and self.snapshotter.save_best
            else None
        )
        if prev is not None:
            if (
                self.snapshotter is not None
                and self.snapshotter.save_best
                and prev_retained is None
            ):
                # a snapshotter assigned AFTER the pending epoch dispatched
                # has no retained buffer for it — self.state is already one
                # epoch ahead, and writing it as the pending epoch's 'best'
                # would be silently wrong
                raise ValueError(
                    "snapshotter with save_best was assigned after an "
                    "epoch dispatched under epoch_sync='deferred'; assign "
                    "it before training starts (the retained state buffer "
                    "is captured at dispatch time)"
                )
            # guard above guarantees this verdict cannot be a stop
            return self._finish_epoch(prev, retained=prev_retained)
        return flushed

    def sync_epoch(self) -> Optional[Dict[str, Any]]:
        """Flush a deferred epoch's metrics (no-op returning None when
        nothing is pending).  Call after a ``run_epoch`` loop: in deferred
        mode to observe the final epoch, and in either mode to park the
        prefetch producer, which has run ahead into an epoch that this
        loop will not run (``run()`` parks it at the decision's stop; a
        ``run_epoch()`` that returns ``stop`` does not, since a caller
        may go on regardless, as a timed benchmark does)."""
        self._park_producer()
        if self._pending_accs is None:
            return None
        accs, self._pending_accs = self._pending_accs, None
        # nothing was dispatched after the pending epoch, so self.state is
        # exactly that epoch's — the retained copy is redundant
        self._retained = None
        try:
            return self._finish_epoch(accs)
        except _RollbackSignal as sig:
            self._execute_rollback(sig.reason)
            return None

    def _retain_state(self):
        """Copy of the CURRENT epoch's snapshot inputs, held until its
        lagged verdict resolves under deferred sync with ``save_best``.

        ``jnp.copy`` (not ``device_put``, which may alias) guarantees fresh
        buffers: the next epoch's train step donates ``self.state``'s.  The
        decision part of the host state is deliberately absent — it is only
        correct AFTER the lagged ``on_epoch_end``, and is merged in at save
        time by :meth:`_finish_epoch`."""
        state = jax.tree_util.tree_map(jnp.copy, self.state)
        return state, self._input_state()

    # -- self-healing (docs/TRAINING.md) -------------------------------------
    def request_stop(self) -> None:
        """Ask the run to stop gracefully at the next step boundary:
        the in-flight dispatch drains, an emergency snapshot is written
        and :class:`TrainingPreempted` raises out of ``run``/``run_epoch``
        (the launcher maps it to exit code ``EXIT_PREEMPTED``).  Safe to
        call from a signal handler (one bool store)."""
        self._preempt_requested = True

    def enable_emergency_snapshots(self) -> None:
        """Retain each sync-mode epoch's START state (one extra copy of
        the train state held per epoch) so a mid-epoch stop writes a
        CONSISTENT emergency snapshot — resume replays the aborted
        epoch exactly.  The launcher enables this whenever it installs
        signal handlers and a snapshotter exists; without it a
        mid-epoch stop snapshots the current (mid-epoch) params, which
        resumes correctly but not byte-exactly."""
        self._emergency_capture = True

    def _retain_epoch_start(self):
        """Fresh copies of the epoch-START restore quadruple: train
        state + decision/loader/prng host state (the same shape a
        snapshot file holds)."""
        state = jax.tree_util.tree_map(jnp.copy, self.state)
        return state, self.host_state()

    def _restore_from(self, state, host: Dict[str, Any]) -> None:
        """The exact-resume contract, shared by ``initialize(snapshot=)``
        rollback and chaos tests: restore train state (re-sharded under
        the placement policy) and the decision/loader/prng host state.
        Re-feeds the ALREADY-COMPILED step — shapes/dtypes/structure are
        unchanged, so restoring compiles nothing new (pinned in tier-1)."""
        self._park_producer()  # before the loader's state is replaced
        st = state if isinstance(state, TrainState) else TrainState(*state)
        if self.parallel is not None:
            st = self.parallel.shard_state(st)
        else:
            # device-place host (numpy) leaves NOW: a numpy argument
            # misses the already-compiled step's executable-cache entry
            # (placement rides the pjit cache key), which would make the
            # "rollback compiles nothing" pin false
            st = jax.tree_util.tree_map(jax.device_put, st)
        self.state = st
        host = host or {}
        if "decision" in host:
            self.decision.load_state_dict(host["decision"])
        if "loader" in host:
            self.loader.load_state_dict(host["loader"])
        if "prng" in host:
            prng.load_state_dict(host["prng"])
        self._host_step = int(self.state.step)

    def _execute_rollback(self, reason: str) -> None:
        """Roll the run back to its last good restore point.

        Source preference: the in-memory epoch-START buffer when one
        was captured (it is always at least as fresh as any snapshot
        file, and detection lands within its epoch, so the buffer
        predates the fault — preferring an older snapshot would
        silently re-run up to ``interval - 1`` healthy epochs), else
        the newest VALID snapshot file.  Bounded by the policy's
        rollback budget — past it (or with no restore point) the typed
        :class:`RollbackExhaustedError` raises, with the give-up gauge
        set for ``znicz-doctor``."""
        self._park_producer()
        pol = self.recovery
        step = self._host_step
        # poisoned in-flight bookkeeping dies with the aborted epoch
        self._pending_accs = None
        self._retained = None
        self._pending_watch = []
        if not pol.budget_left():
            pol.note_give_up(
                reason, step=step, why="rollback budget spent"
            )
            raise RollbackExhaustedError(
                f"anomaly {reason!r} at step {step}: rollback budget "
                f"({pol.max_rollbacks}) spent — giving up"
            )
        state = host = None
        source = None
        if self._epoch_start is not None:
            state, host = self._epoch_start
            source = "epoch-start buffer"
        if source is None and self.snapshotter is not None:
            path = find_latest_valid(
                self.snapshotter.directory, prefix=self.snapshotter.prefix
            )
            if path is not None:
                try:
                    state, host = load_snapshot(path)
                    source = path
                except (SnapshotCorruptError, ValueError):
                    # verified then unreadable (raced delete / injected
                    # load fault): nothing left to restore from
                    self.logger.exception(
                        "rollback snapshot %s unreadable", path
                    )
        if source is None:
            pol.note_give_up(
                reason,
                step=step,
                why="no valid snapshot or retained epoch-start state",
            )
            raise RollbackExhaustedError(
                f"anomaly {reason!r} at step {step}: no valid snapshot "
                "or retained epoch-start state to roll back to"
            )
        self._restore_from(state, host)
        if pol.perturb:
            # advance the shuffle stream so the replayed window draws a
            # different permutation — a data-order-dependent blowup
            # doesn't deterministically recur (costs golden-exactness;
            # perturb=False keeps the replay byte-identical)
            gen = prng.get(self.loader.rand_name)
            gen.permutation(
                max(self.loader.class_lengths.get(TRAIN, 1), 1)
            )
        pol.note_rollback(reason, step=step, source=str(source))
        self.info(
            "rolled back to %s after %s at step %d "
            "(rollback %d/%d, lr_scale %.4g)",
            source, reason, step,
            pol.rollbacks_used, pol.max_rollbacks, pol.lr_scale,
        )

    def _graceful_exit(self, *, mid_epoch: bool) -> None:
        """Finish a requested stop: write the emergency snapshot (the
        epoch-START buffer when stopping mid-epoch so the resume is
        exact; the current state between epochs) and raise the typed
        :class:`TrainingPreempted`."""
        self._park_producer()
        path = None
        if self.snapshotter is not None:
            if mid_epoch and self._epoch_start is not None:
                state, host = self._epoch_start
            else:
                # deferred mode: flush the pending epoch first so the
                # snapshot's decision state is consistent with the
                # params it rides with.  Mid-epoch, self.state is
                # ALREADY the next epoch's partial state, so the flush
                # must write from the retained pending-epoch buffer
                # (sync_epoch would drop it and save torn params).
                retained, self._retained = self._retained, None
                if self._pending_accs is not None:
                    accs, self._pending_accs = self._pending_accs, None
                    try:
                        self._finish_epoch(accs, retained=retained)
                    # stopping anyway: a rollback is moot mid-shutdown
                    except _RollbackSignal:  # znicz-check: disable=ZNC008
                        pass
                    except Exception:
                        self.logger.exception(
                            "pending-epoch flush failed during "
                            "graceful stop"
                        )
                if mid_epoch and retained is not None:
                    # deferred + mid-epoch: the retained buffer (the
                    # flushed epoch's end state) plus the now-current
                    # decision IS the next epoch's consistent START
                    # quadruple — resume replays the aborted epoch
                    r_state, r_host = retained
                    state, host = r_state, {
                        "decision": self.decision.state_dict(),
                        "loader": r_host["loader"],
                        "prng": r_host["prng"],
                    }
                else:
                    state, host = self.state, self.host_state()
            try:
                path = self.snapshotter.save(state, host, tag="emergency")
                self.info("graceful stop: emergency snapshot %s", path)
            except SnapshotWriteError:
                self.logger.exception("emergency snapshot write failed")
        raise TrainingPreempted(
            "training stopped on request (SIGTERM/SIGINT); resume from "
            "the emergency snapshot (launcher: --resume auto)",
            snapshot_path=path,
        )

    def _epoch_batches(self):
        """One epoch's batches, placed on the device.  With
        ``prefetch_batches`` a producer thread fetches and places them
        ahead of the steps, and goes on past the epoch's end: the next
        call finds its first batches on the device already."""
        if self.prefetch_batches and self._feed is not None:
            return self._feed.epoch()
        self._park_producer()
        stage_item = _batch_stager(
            None
            if self.target == "input"
            else weakref.WeakMethod(self._batch_target),
            self.parallel.shard_batch
            if self.parallel is not None
            else jnp.asarray,
            self._h2d_probe,
        )
        if not self.prefetch_batches:
            return map(stage_item, self.loader.epoch())
        self._feed = CarriedEpochs(
            self.loader, self.prefetch_batches, stage_item
        )
        return self._feed.epoch()

    def _run_epoch_stepwise(self) -> Dict[str, jax.Array]:
        accs: Dict[str, jax.Array] = {}  # per-split on-device accumulators
        # lagged per-step anomaly watch: host copies start at dispatch,
        # values are read a few steps later — detection without a sync
        watch_q: deque = deque()
        t_prev = time.perf_counter()
        for split, x, y, mask in self._epoch_batches():
            if self._preempt_requested:
                # the previous dispatch is the in-flight step; it
                # drains on its own — stop BEFORE dispatching another
                raise _PreemptSignal()
            with self.timer.phase(f"dispatch/{split}"):
                acc = accs.get(split)
                if acc is None:
                    acc = self._acc_init()
                if split == TRAIN:
                    lr_scale = (
                        self.lr_policy(1.0, self._host_step)
                        if self.lr_policy
                        else 1.0
                    )
                    if self.recovery is not None:
                        # rollback LR backoff composes with the policy
                        lr_scale *= self.recovery.lr_scale
                    self.state, acc, watch = self._train_step(
                        self.state, x, y, mask, lr_scale, acc, self._ctx
                    )
                    self._host_step += 1
                else:
                    watch = None
                    acc = self._eval_step(
                        self.state.params, x, y, mask, acc, self._ctx
                    )
                accs[split] = acc
            # consumer-side step wall (prefetch wait + dispatch + host
            # bookkeeping): the denominator of the pipeline attribution
            now = time.perf_counter()
            step_wall = now - t_prev
            t_prev = now
            self._step_wall.observe(step_wall)
            if watch is not None and self.anomaly is not None:
                if hasattr(watch, "copy_to_host_async"):
                    watch.copy_to_host_async()
                watch_q.append(
                    (self._host_step - 1, watch, step_wall)
                )
                if len(watch_q) > 2:  # ~2 steps of transfer lag
                    self._check_recovery(
                        self._feed_watch(*watch_q.popleft())
                    )
        while watch_q:
            self._check_recovery(self._feed_watch(*watch_q.popleft()))
        return accs

    def _feed_watch(self, step, watch, step_seconds=None) -> list:
        """Hand one lagged watch vector to the anomaly detector; returns
        the verdicts it raised (the recovery policy's input).  The read
        is of an already-transferred tiny array (the async copy started
        at dispatch); the detector must never kill training — only a
        returned verdict may (via the recovery policy's typed path)."""
        if self.anomaly is None:
            return []
        try:
            vals = np.asarray(
                jax.device_get(watch),  # znicz-check: disable=ZNC007
                np.float32,
            )
            loss = float(vals[0])
            grad_norm = float(vals[1])
        except Exception:
            self.logger.exception("anomaly watch feed failed")
            return []
        if faults.fire("train.step_nan"):
            # behavioral chaos point: the detector (and the recovery
            # policy behind it) sees a non-finite loss without actually
            # poisoning device state — the rollback path's CI fixture
            loss = float("nan")
        try:
            return self.anomaly.observe_step(
                int(step),
                loss=loss,
                grad_norm=grad_norm,
                step_seconds=step_seconds,
            )
        except Exception:
            self.logger.exception("anomaly watch feed failed")
            return []

    def _drain_watches(self) -> list:
        """Feed the scanned epochs' pending watch stacks ([n_steps, 2])
        to the detector — called at the epoch's metric sync, where a
        device fetch already happens.  Returns the raised verdicts."""
        pending, self._pending_watch = self._pending_watch, []
        if self.anomaly is None:
            return []
        raised: list = []
        for start_step, watches in pending:
            try:
                rows = np.asarray(
                    jax.device_get(watches),  # znicz-check: disable=ZNC007
                    np.float32,
                )
            except Exception:
                self.logger.exception("anomaly watch drain failed")
                continue
            for i, row in enumerate(rows):
                raised.extend(
                    self._feed_scan_row(start_step + i, row)
                )
        return raised

    def _feed_scan_row(self, step: int, row) -> list:
        loss = float(row[0])
        if faults.fire("train.step_nan"):
            loss = float("nan")
        try:
            return self.anomaly.observe_step(
                step, loss=loss, grad_norm=float(row[1])
            )
        except Exception:
            self.logger.exception("anomaly watch drain failed")
            return []

    def _check_recovery(self, anomalies: list) -> None:
        """Route fresh verdicts through the recovery policy; a
        rollback-worthy one aborts the epoch via :class:`_RollbackSignal`
        (caught in :meth:`run_epoch`)."""
        if not anomalies or self.recovery is None:
            return
        reason = self.recovery.should_rollback(anomalies)
        if reason is not None:
            raise _RollbackSignal(reason)

    def _finish_epoch(
        self, accs: Dict[str, jax.Array], retained=None
    ) -> Dict[str, Any]:
        # scanned-epoch watch vectors resolve here, where a device
        # fetch happens anyway (their async copies started at dispatch);
        # a rollback-worthy verdict aborts BEFORE the poisoned metrics
        # reach the decision
        self._check_recovery(self._drain_watches())
        with self.timer.phase("metrics_sync"):
            # one tiny existing-buffer fetch per split (no per-batch
            # syncs) — the per-EPOCH fetch this design exists to bound
            for split, acc in accs.items():
                self.decision.add_minibatch(
                    split,
                    _decode_metrics(
                        jax.device_get(acc),  # znicz-check: disable=ZNC007
                        self._metric_names,
                    ),
                )
        verdict = self.decision.on_epoch_end()
        if self.snapshotter is not None:
            # called on EVERY process (the device->host readback may be a
            # collective for cross-host-sharded params); only the writer
            # process (coordinator) touches the filesystem.  Under deferred
            # sync with save_best, ``retained`` carries the epoch-N buffers
            # (self.state already holds epoch N+1); key order matches
            # host_state() so snapshot files are byte-identical to sync mode.
            if retained is not None:
                snap_state, host_extra = retained
                snap_host = {
                    "decision": self.decision.state_dict(),
                    "loader": host_extra["loader"],
                    "prng": host_extra["prng"],
                }
            else:
                snap_state, snap_host = self.state, self.host_state()
            self.snapshotter.maybe_save(
                snap_state,
                snap_host,
                epoch=self.decision.epoch - 1,
                improved=verdict["improved"],
            )
        if not getattr(self, "_coordinator", True):
            return verdict  # services are host-side: coordinator-only
        for service in self.services:
            try:
                service.on_epoch(self, verdict)
            except Exception:  # services must never kill training
                self.logger.exception(
                    "service %s failed", type(service).__name__
                )
        return verdict

    def evaluate(self, split: str = "test", *, confusion: bool = False):
        """Standalone evaluation pass over one split.

        Returns {"loss", "n_err", "err_pct", "n_samples"} plus a summed
        ``confusion`` matrix (rows = truth) when requested — the reference
        EvaluatorSoftmax's full metric set (SURVEY.md 2.3).
        """
        if self.state is None:
            self.initialize()
        self._park_producer()  # this pass reads the loader on this thread
        if self.loader.class_lengths.get(split, 0) == 0:
            # evaluating zero samples would report a silent perfect score
            raise ValueError(
                f"evaluate({split!r}): the loader has no samples in that "
                "split (available: "
                f"{sorted(k for k, n in self.loader.class_lengths.items() if n)})"
            )
        use_conf = (
            confusion
            and self.loss_function == "softmax"
            and self._eval_conf_step is not None
        )
        # shuffle=False: evaluation is read-only — it must not advance the
        # loader's shuffle stream (resume determinism)
        put = (
            self.parallel.shard_batch
            if self.parallel is not None
            else jnp.asarray
        )
        acc = self._acc_init()
        conf = None
        for mb in self.loader.batches(split, shuffle=False):
            x = put(mb.data)
            y = x if self.target == "input" else put(self._batch_target(mb))
            mask = put(mb.mask)
            if use_conf:
                if conf is None:
                    nc = int(np.prod(self.model.output_shape))
                    conf = self._put_replicated(np.zeros((nc, nc), np.int32))
                acc, conf = self._eval_conf_step(
                    self.state.params, x, y, mask, acc, conf, self._ctx
                )
            else:
                acc = self._eval_step(
                    self.state.params, x, y, mask, acc, self._ctx
                )
        # one (or two, with confusion) existing-buffer syncs for the split
        m = _decode_metrics(jax.device_get(acc), self._metric_names)
        n = m.get("n_samples", 0.0)
        n_err = m.get("n_err", 0.0)
        result = {
            "n_samples": n,
            "n_err": n_err,
            "err_pct": 100.0 * n_err / max(n, 1.0),
            "loss": m.get("loss", 0.0),
        }
        if conf is not None:
            result["confusion"] = np.asarray(jax.device_get(conf))
        return result

    def run(self) -> Decision:
        """Train until the Decision stops; returns it (history, best)."""
        if self.state is None:
            self.initialize()
        clock = Stopwatch()
        while True:
            verdict = self.run_epoch()
            if verdict is None:  # deferred sync: no completed epoch yet
                continue
            s = verdict["summary"]
            parts = [
                f"{split} err={m['err_pct']:.2f}% loss={m['loss']:.4f}"
                if self.loss_function == "softmax"
                else f"{split} loss={m['loss']:.6f}"
                for split, m in s.items()
            ]
            self.info(
                "epoch %d [%.1fs]: %s%s",
                self.decision.epoch - 1,
                clock.elapsed(),
                "; ".join(parts),
                " *" if verdict["improved"] else "",
            )
            if verdict["stop"]:
                self.info(
                    "stopping: best=%s at epoch %d",
                    verdict["best_value"],
                    verdict["best_epoch"],
                )
                self._park_producer()  # no next epoch to run ahead into
                return self.decision
