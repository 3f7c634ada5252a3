"""Non-backprop workflows: Kohonen SOM and RBM.

Parity with the reference's non-GD learning paths [SURVEY.md 2.2 rows
"Kohonen SOM", "RBM"; §7 "Hard parts"]: the learning rule IS the trainer
(KohonenTrainer's winner-take-all + neighborhood update; rbm_units' CD-k
updaters), so these workflows replace autodiff with the custom update
functions from :mod:`znicz_tpu.ops.kohonen` / :mod:`znicz_tpu.ops.rbm`,
while reusing the loader/decision/snapshotter machinery.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from znicz_tpu.core import backend, prng
from znicz_tpu.loader.base import TRAIN, Loader
from znicz_tpu.nn.decision import Decision
from znicz_tpu.nn.train_state import TrainState
from znicz_tpu.ops import kohonen as kh, rbm as rbm_op
from znicz_tpu.workflow.snapshotter import Snapshotter
from znicz_tpu.workflow.workflow import Workflow


class _NoModel:
    """Placeholder satisfying Workflow's model attribute for custom steps."""

    params: list = []
    hyper: list = []


class KohonenWorkflow(Workflow):
    """Batch-SOM training (znicz/samples/DemoKohonen; BASELINE configs[4]).

    Metric: quantization error (mean squared distance to the winning unit)
    reported as ``loss`` so Decision/snapshot semantics carry over.
    """

    def __init__(
        self,
        loader: Loader,
        *,
        sx: int = 8,
        sy: int = 8,
        total_epochs: int = 20,
        lr0: float = 0.1,
        lr1: float = 0.01,
        sigma1: float = 1.0,
        decision: Optional[Decision] = None,
        snapshotter: Optional[Snapshotter] = None,
        parallel=None,
        prefetch_batches: int = 2,
        epoch_sync: str = "sync",
        rand_name: str = "default",
        impl: str = "auto",  # "pallas" | "xla" | "auto" (pallas on TPU)
        name: str = "KohonenWorkflow",
    ):
        super().__init__(
            loader,
            _NoModel(),
            loss_function="mse",
            target="labels",
            decision=decision
            or Decision(metric="loss", max_epochs=total_epochs),
            snapshotter=snapshotter,
            parallel=parallel,
            prefetch_batches=prefetch_batches,
            epoch_sync=epoch_sync,
            name=name,
        )
        self.sx, self.sy = sx, sy
        self.total_epochs = total_epochs
        self.lr0, self.lr1, self.sigma1 = lr0, lr1, sigma1
        self.rand_name = rand_name
        self.impl = impl
        self._n_input = int(jnp.prod(jnp.asarray(loader.sample_shape)))

    def _batch_target(self, mb):
        return np.zeros(len(mb.mask), np.int32)  # unused host-side dummy

    def _build_steps(self):
        coords = kh.grid_coords(self.sx, self.sy)
        n_steps_per_epoch = max(self.loader.n_minibatches(TRAIN), 1)
        total_steps = self.total_epochs * n_steps_per_epoch
        # fused kernel partitioning rule: under a sharded batch the kernel
        # accumulates local (num, den) partials inside shard_map and psums
        # them over the data axis — the fast path survives data parallelism
        use_pallas = self.impl == "pallas" or (
            self.impl == "auto" and backend.on_tpu()
        )
        pallas_mesh = (
            self.parallel.mesh
            if use_pallas and self.parallel is not None
            else None
        )
        if use_pallas:
            from znicz_tpu.ops.pallas import kohonen as pallas_kh

        def train_step(state: TrainState, x, y, mask, lr_scale):
            x = x.reshape(x.shape[0], -1)
            lr, sigma = kh.decay_schedule(
                state.step,
                total_steps,
                lr0=self.lr0,
                lr1=self.lr1,
                sigma1=self.sigma1,
                sx=self.sx,
                sy=self.sy,
            )
            if use_pallas:
                win = kh.winners(state.params, x)
                params = pallas_kh.train_step(
                    state.params,
                    x,
                    coords,
                    learning_rate=lr * lr_scale,
                    sigma=sigma,
                    mask=mask,
                    mesh=pallas_mesh,
                )
            else:
                params, win = kh.train_step(
                    state.params,
                    x,
                    coords,
                    learning_rate=lr * lr_scale,
                    sigma=sigma,
                    mask=mask,
                )
            metrics = self._qe(params, x, win, mask)
            return state._replace(params=params, step=state.step + 1), metrics

        def eval_step(params, x, y, mask):
            x = x.reshape(x.shape[0], -1)
            win = kh.winners(params, x)
            return self._qe(params, x, win, mask)

        self._finalize_steps(
            train_step, eval_step, ["loss", "n_samples", "n_err"]
        )

    @staticmethod
    def _qe(params, x, win, mask):
        d2 = jnp.sum(jnp.square(x - params["weights"][win]), axis=1)
        n = jnp.maximum(jnp.sum(mask), 1.0)
        return {
            "loss": jnp.sum(d2 * mask) / n,
            "n_samples": n,
            "n_err": jnp.zeros((), jnp.int32),
        }

    def _create_initial_state(self) -> TrainState:
        params = kh.init_params(
            self.sx, self.sy, self._n_input, rand_name=self.rand_name
        )
        return TrainState.create(params, prng.get("workflow").key())

    def weights_map(self):
        """[sy, sx, features] view of the trained map (for plotting)."""
        w = np.asarray(self.state.params["weights"])
        return w.reshape(self.sy, self.sx, -1)


class RBMWorkflow(Workflow):
    """Bernoulli RBM with CD-k (znicz/samples MNIST RBM; BASELINE configs[2]).

    Metric: masked reconstruction error as ``loss``.
    """

    def __init__(
        self,
        loader: Loader,
        *,
        n_hidden: int = 64,
        learning_rate: float = 0.1,
        cd_k: int = 1,
        max_epochs: int = 20,
        decision: Optional[Decision] = None,
        snapshotter: Optional[Snapshotter] = None,
        parallel=None,
        prefetch_batches: int = 2,
        epoch_sync: str = "sync",
        rand_name: str = "default",
        impl: str = "auto",  # "pallas" | "xla" | "auto" (pallas on TPU)
        name: str = "RBMWorkflow",
    ):
        super().__init__(
            loader,
            _NoModel(),
            loss_function="mse",
            target="labels",
            decision=decision or Decision(metric="loss", max_epochs=max_epochs),
            snapshotter=snapshotter,
            parallel=parallel,
            prefetch_batches=prefetch_batches,
            epoch_sync=epoch_sync,
            name=name,
        )
        self.n_hidden = n_hidden
        self.learning_rate = learning_rate
        self.cd_k = cd_k
        self.rand_name = rand_name
        self.impl = impl
        self._n_visible = int(jnp.prod(jnp.asarray(loader.sample_shape)))

    def _batch_target(self, mb):
        return np.zeros(len(mb.mask), np.int32)  # unused host-side dummy

    def _build_steps(self):
        from znicz_tpu.ops.pallas import rbm as pallas_rbm

        # fused CD-k kernel (hardware RNG, whole Gibbs chain in VMEM) when
        # on TPU and the problem fits the VMEM budget; the psum rule keeps
        # it available under a sharded batch (see ops/pallas/rbm.py)
        # the kernel runs per data-axis SHARD, so the VMEM check uses the
        # per-shard batch — a sharded big batch can still take the kernel
        shard_batch = self.loader.max_minibatch_size
        if self.parallel is not None:
            shard_batch = -(-shard_batch // self.parallel.n_data)
        use_pallas = self.impl == "pallas" or (
            self.impl == "auto"
            and backend.on_tpu()
            and pallas_rbm.fits_vmem(
                shard_batch, self._n_visible, self.n_hidden
            )
        )
        pallas_mesh = (
            self.parallel.mesh
            if use_pallas and self.parallel is not None
            else None
        )

        def train_step(state: TrainState, x, y, mask, lr_scale):
            v0 = x.reshape(x.shape[0], -1)
            if use_pallas:
                params, err = pallas_rbm.cd_step(
                    state.params,
                    v0,
                    state.step,
                    learning_rate=self.learning_rate * lr_scale,
                    cd_k=self.cd_k,
                    mask=mask,
                    mesh=pallas_mesh,
                )
            else:
                rng = jax.random.fold_in(state.key, state.step)
                params, err = rbm_op.cd_step(
                    state.params,
                    v0,
                    rng,
                    learning_rate=self.learning_rate * lr_scale,
                    cd_k=self.cd_k,
                    mask=mask,
                )
            metrics = {
                "loss": err,
                "n_samples": jnp.maximum(jnp.sum(mask), 1.0),
                "n_err": jnp.zeros((), jnp.int32),
            }
            return state._replace(params=params, step=state.step + 1), metrics

        def eval_step(params, x, y, mask):
            v0 = x.reshape(x.shape[0], -1)
            v_probs = rbm_op.visible_probs(
                params, rbm_op.hidden_probs(params, v0)
            )
            per = jnp.mean(jnp.square(v0 - v_probs), axis=1)
            n = jnp.maximum(jnp.sum(mask), 1.0)
            return {
                "loss": jnp.sum(per * mask) / n,
                "n_samples": n,
                "n_err": jnp.zeros((), jnp.int32),
            }

        self._finalize_steps(
            train_step, eval_step, ["loss", "n_samples", "n_err"]
        )

    def _create_initial_state(self) -> TrainState:
        params = rbm_op.init_params(
            self._n_visible, self.n_hidden, rand_name=self.rand_name
        )
        return TrainState.create(params, prng.get("workflow").key())
