"""Data (+ optional tensor) parallel placement policy for workflows.

The TPU-native replacement for the reference's asynchronous parameter-server
DP (SURVEY.md 2.5 row "Data parallel"): the jitted train step runs SPMD over
the mesh; XLA turns the gradient contraction into an all-reduce over ICI.
Synchronous by construction — the convergence-relevant behavior
(every sample contributes once per epoch, one consistent model) matches the
reference's centralized aggregation.

Tensor parallelism (absent in the reference, SURVEY.md 2.5): FC/conv weights
whose output dim is divisible by the ``model`` axis and larger than
``tp_min_features`` are sharded on that dim; GSPMD propagates activations'
shardings and inserts the collectives.
"""

from __future__ import annotations

from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from znicz_tpu.parallel.mesh import (
    DATA_AXIS,
    MODEL_AXIS,
    make_mesh,
    replicated,
)


def cnn_tp_rules(model, n_model: int, *, tp_min_features: int = 1024):
    """Channel-aware tensor-parallel placement for conv/FC models.

    Megatron-style alternation over the model's weighted layers: a layer
    whose output channels/features divide the ``model`` axis is COLUMN
    sharded (conv ``[ky, kx, in, out]`` on ``out``, FC ``[in, out]`` on
    ``out``, bias along); the NEXT weighted layer is ROW sharded on its
    input dim, so XLA contracts locally and psums partial products —
    conv kernels, the layers that dominate a CNN's FLOPs, stop
    replicating.  FC layers additionally honor ``tp_min_features`` (the
    size heuristic's threshold) so small heads stay replicated; conv
    layers shard on divisibility alone (their FLOPs justify it at any
    width).  Returns a ``param_rules`` callable for :class:`DataParallel`.
    """
    import re

    from znicz_tpu.parallel.mesh import MODEL_AXIS as M

    specs = {}
    col_prev = False
    for i, params in enumerate(model.params):
        w = params.get("weights") if isinstance(params, dict) else None
        if w is None or w.ndim < 2:
            continue
        is_conv = w.ndim == 4
        out_dim = w.shape[-1]
        in_dim = w.shape[-2] if is_conv else w.shape[0]
        if col_prev and is_conv and in_dim % n_model == 0:
            # row-parallel follower: shard the input/contraction dim.
            # Conv only — an FC after a flatten sees the channel-sharded
            # activations INTERLEAVED through its h*w*c input dim
            # (channel-minor flatten), so contiguous dim-0 weight sharding
            # would force a reshard instead of a local contract + psum
            specs[(i, "weights")] = P(None, None, M, None)
            specs[(i, "bias")] = P()
            col_prev = False
        elif out_dim % n_model == 0 and (
            is_conv or out_dim >= tp_min_features
        ):
            specs[(i, "weights")] = P(*([None] * (w.ndim - 1)), M)
            specs[(i, "bias")] = P(M)
            col_prev = True
        else:
            col_prev = False

    pat = re.compile(r"\[(\d+)\]\['(\w+)'\]")

    def rules(path: str, leaf):
        m = pat.search(path)
        if not m:
            return P()
        return specs.get((int(m.group(1)), m.group(2)), P())

    return rules


class DataParallel:
    """Placement policy: how batches and params land on the mesh.

    ``tp``: enable tensor-parallel weight sharding over the ``model`` axis.
    """

    def __init__(
        self,
        mesh: Optional[Mesh] = None,
        *,
        tp: bool = False,
        tp_min_features: int = 1024,
        param_rules=None,
    ):
        self.mesh = mesh if mesh is not None else make_mesh()
        if jax.process_count() > 1:
            # the loader's per-process row contract only holds when each
            # process owns one contiguous block of the data axis
            from znicz_tpu.parallel.mesh import (
                verify_process_contiguous_data_axis,
            )

            verify_process_contiguous_data_axis(self.mesh)
        self.tp = tp and self.mesh.shape[MODEL_AXIS] > 1
        self.tp_min_features = tp_min_features
        # param_rules: callable (path_str, leaf) -> PartitionSpec or None.
        # Explicit model-aware placement (e.g. the transformer's QKV-head /
        # row-column FFN rules) — None falls through to the size heuristic.
        self.param_rules = param_rules

    @property
    def n_data(self) -> int:
        return self.mesh.shape[DATA_AXIS]

    def scope(self):
        """Context a step is TRACED under, so that an op the partitioner
        cannot split (a Pallas kernel) finds the mesh
        (``jax.sharding.get_abstract_mesh()``) and runs per shard under
        ``shard_map``.  The axes are ``Auto``: nothing else reads it."""
        return jax.sharding.use_abstract_mesh(self.mesh.abstract_mesh)

    # -- batches -----------------------------------------------------------
    def shard_batch(self, arr, *, batch_dim: int = 0) -> jax.Array:
        """Place a host batch sharded over the data axis (the batch dim
        must divide by the axis size; the loader's padded static batches
        ensure a constant batch size, so pick minibatch_size accordingly).
        ``batch_dim=1`` serves epoch-stacked [n_steps, B, ...] payloads
        (the workflow's scanned dispatch).

        Multi-host (process_count > 1): ``arr`` is this process's LOCAL
        slice of the global batch — the loader's per-process shard contract
        (Loader.set_process_shard) serves each process rows
        ``[p*B/P, (p+1)*B/P)`` of every global minibatch, the same rows its
        addressable mesh devices own.  The pieces are assembled into ONE
        global array without any cross-host data movement (the reference's
        master never re-collected sample tensors either — SURVEY.md 3.4
        assigns index ranges to slaves)."""
        arr = np.asarray(arr)
        nproc = jax.process_count()
        if nproc > 1:
            gshape = list(arr.shape)
            gshape[batch_dim] *= nproc
            if gshape[batch_dim] % self.n_data:
                raise ValueError(
                    f"global batch {gshape[batch_dim]} not divisible by "
                    f"data axis {self.n_data}"
                )
            spec = [None] * arr.ndim
            spec[batch_dim] = DATA_AXIS
            return jax.make_array_from_process_local_data(
                NamedSharding(self.mesh, P(*spec)),
                arr,
                global_shape=tuple(gshape),
            )
        if arr.shape[batch_dim] % self.n_data:
            raise ValueError(
                f"batch {arr.shape[batch_dim]} not divisible by data axis "
                f"{self.n_data}; choose minibatch_size as a multiple"
            )
        spec = [None] * arr.ndim
        spec[batch_dim] = DATA_AXIS
        return jax.device_put(arr, NamedSharding(self.mesh, P(*spec)))

    def put_replicated(self, arr) -> jax.Array:
        """Place identical-on-every-process host data fully replicated over
        the mesh (epoch accumulators, loader device contexts) so jitted
        steps see consistently-placed global arrays on multi-host jobs."""
        return jax.device_put(arr, replicated(self.mesh))

    # -- params ------------------------------------------------------------
    def _param_spec(self, path: str, leaf) -> P:
        if self.param_rules is not None:
            spec = self.param_rules(path, leaf)
            if spec is not None:
                return spec
        if (
            self.tp
            and hasattr(leaf, "ndim")
            and leaf.ndim >= 1
            and leaf.shape[-1] >= self.tp_min_features
            and leaf.shape[-1] % self.mesh.shape[MODEL_AXIS] == 0
        ):
            # shard the output-features dim: column-parallel FC / conv
            return P(*([None] * (leaf.ndim - 1)), MODEL_AXIS)
        return P()

    def shard_state(self, state):
        """Place a TrainState: params/velocity per policy, scalars/key
        replicated.

        Leaves go device->host->mesh: a numpy source is the one input kind
        ``jax.device_put`` accepts for shardings that span non-addressable
        devices (multi-host), and every process holds the identical values
        (same seeds), so the host round-trip is also the correct global
        placement.  One-time cost at initialize, not in the hot loop."""
        import jax.numpy as jnp

        def put(leaf, sharding):
            if isinstance(leaf, jax.Array) and jnp.issubdtype(
                leaf.dtype, jax.dtypes.prng_key
            ):
                data = jax.device_put(
                    np.asarray(jax.random.key_data(leaf)), sharding
                )
                return jax.random.wrap_key_data(
                    data, impl=jax.random.key_impl(leaf)
                )
            return jax.device_put(np.asarray(leaf), sharding)

        def place(path, leaf):
            spec = self._param_spec(jax.tree_util.keystr(path), leaf)
            return put(leaf, NamedSharding(self.mesh, spec))

        params = jax.tree_util.tree_map_with_path(place, state.params)
        velocity = jax.tree_util.tree_map_with_path(place, state.velocity)
        rep = replicated(self.mesh)
        return state._replace(
            params=params,
            velocity=velocity,
            step=put(state.step, rep),
            key=put(state.key, rep),
        )
