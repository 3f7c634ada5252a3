"""Data-axis pool sharding for device-resident loaders.

The HBM pool shards over the mesh's DATA axis — each device holds 1/D of
every split, so dataset capacity is ``n_data x one chip's free HBM``
(max rows ~= n_data * HBM_free / bytes_per_sample) instead of one chip's.
Locality is by construction, so no collective ever moves pool-sized data:

- **Per-shard sampling.**  Each split is partitioned into D equal row
  blocks; batch position block ``s`` only draws from shard ``s``'s rows
  (every sample still appears exactly once per epoch — minibatch
  COMPOSITION mixes within shards instead of globally).
- **Local addresses.**  Minibatch payloads carry addresses into the
  owning device's pool block, and the gather/preproc runs inside a
  ``shard_map`` over the data axis.
- **Per-process placement.**  Multi-host jobs ship only their own shards'
  rows; ``DataParallel.shard_batch`` assembles the global pool array.

Mixin contract (see ``FullBatchLoader`` / ``ImageNetLoader``): subclasses
set ``self.wants_data_shards`` when the mode is on, implement
``_pool_split_arrays() -> {split: [n, ...] array}``, build payloads with
``_local_addr``, and wrap their per-shard preproc with
``_shard_map_pre``.  ``Workflow.initialize`` calls ``set_data_shards``
with the mesh's data-axis size before placing the device context.
"""

from __future__ import annotations

from typing import Dict

import numpy as np

from znicz_tpu.loader.base import TRAIN, pool_offsets


class PoolShardedMixin:
    """Per-shard sampling + sharded pool placement (see module docstring)."""

    data_shards = 1

    def _pool_split_arrays(self) -> Dict[str, np.ndarray]:
        raise NotImplementedError

    # -- shard layout --------------------------------------------------------
    def set_data_shards(self, n: int) -> None:
        """Partition every split into ``n`` equal row blocks (shard s of a
        split owns rows [s*len/n, (s+1)*len/n)); sampling becomes
        per-shard so batch position block s only references shard s."""
        if self.balanced:
            raise ValueError(
                "pool sharding is incompatible with balanced=True (the "
                "class-balanced shuffle is a global permutation; per-shard "
                "sampling owns the batch layout)"
            )
        bs = self.max_minibatch_size
        if bs % n:
            raise ValueError(
                f"pool sharding: minibatch_size {bs} not divisible by the "
                f"data axis {n}"
            )
        arrays = self._pool_split_arrays()
        for split, arr in arrays.items():
            if len(arr) % bs:
                raise ValueError(
                    f"pool sharding: split {split!r} has {len(arr)} rows, "
                    f"not a multiple of minibatch_size {bs} (static equal "
                    "per-shard chunks need full batches; pad or trim the "
                    "split)"
                )
        self.data_shards = int(n)
        self._order.clear()  # orders must be rebuilt in blocked layout
        # per-device block layout = the SHARED pool ordering contract
        # applied to one shard's chunk of each split
        self._local_split_offset = pool_offsets(
            {s: arr[: len(arr) // n] for s, arr in arrays.items()}
        )

    def _blocked_order(self, per_shard_rows: np.ndarray) -> np.ndarray:
        """[D, c] per-shard row ids -> epoch order where batch b's position
        block s holds shard s's rows [b*B/D, (b+1)*B/D)."""
        d, c = per_shard_rows.shape
        rows_per = self.max_minibatch_size // d
        steps = c // rows_per
        return (
            per_shard_rows.reshape(d, steps, rows_per)
            .transpose(1, 0, 2)
            .reshape(-1)
        )

    def _split_order(self, split: str) -> np.ndarray:
        if self.data_shards <= 1:
            return super()._split_order(split)
        n = self.class_lengths[split]
        order = self._order.get(split)
        if order is None or len(order) != n:
            c = n // self.data_shards
            order = self._blocked_order(
                np.arange(n).reshape(self.data_shards, c)
            )
            self._order[split] = order
        return order

    def reshuffle(self, split: str = TRAIN) -> None:
        if self.data_shards <= 1:
            return super().reshuffle(split)
        n = self.class_lengths.get(split, 0)
        if not n:
            return
        from znicz_tpu.core import prng

        gen = prng.get(self.rand_name)
        c = n // self.data_shards
        per_shard = np.stack(
            [s * c + gen.permutation(c) for s in range(self.data_shards)]
        )
        self._order[split] = self._blocked_order(per_shard)

    def _validate_batch_indices(self, idx: np.ndarray, split: str) -> None:
        if self.data_shards <= 1:
            return
        c = self.class_lengths[split] // self.data_shards
        rows_per = len(idx) // self.data_shards
        expected = np.repeat(np.arange(self.data_shards), rows_per)
        if not np.array_equal(idx // c, expected):
            raise AssertionError(
                "pool-sharded alignment violated: batch position block s "
                "must only reference data-axis shard s (a local gather "
                "would silently fetch wrong rows)"
            )

    def _local_addr(self, indices: np.ndarray, split: str) -> np.ndarray:
        """Dataset indices -> addresses within the owning device's pool
        block (split-chunk offset + position inside shard s's chunk)."""
        idx = np.asarray(indices, np.int64)
        c = self.class_lengths[split] // self.data_shards
        return (self._local_split_offset[split] + idx % c).astype(np.int32)

    # -- placement -----------------------------------------------------------
    def _local_pool(self) -> np.ndarray:
        """Shard-major pool rows owned by THIS process: for each of its
        data-axis shards, each split's chunk in the shared pool order
        (one allocation, filled in place — a transient 2x host copy would
        defeat this mode for exactly the huge datasets it targets)."""
        d = self.data_shards
        arrays = self._pool_split_arrays()
        lo = self.process_index * d // self.process_count
        hi = (self.process_index + 1) * d // self.process_count
        names = sorted(arrays)  # pool_offsets/pool_concat ordering contract
        chunk = {name: len(arrays[name]) // d for name in names}
        block = sum(chunk.values())
        first = arrays[names[0]]
        out = np.empty(
            ((hi - lo) * block,) + tuple(first.shape[1:]), first.dtype
        )
        row = 0
        for s in range(lo, hi):
            for name in names:
                c = chunk[name]
                out[row: row + c] = arrays[name][s * c:(s + 1) * c]
                row += c
        return out

    def place_device_context(self, parallel):
        if not self.wants_data_shards:
            return super().place_device_context(parallel)
        if parallel is None:
            raise ValueError(
                "pool-sharded loaders need parallel=DataParallel(mesh)"
            )
        if self.data_shards != parallel.n_data:
            raise ValueError(
                f"pool sharding: set_data_shards({parallel.n_data}) was "
                f"not applied (have {self.data_shards}); initialize the "
                "workflow instead of placing the context by hand"
            )
        self._mesh = parallel.mesh
        # shard the pool rows over the data axis: device_context() returns
        # ONLY this process's shards' rows (the one source of the sharded
        # pool layout), shard_batch assembles the global array
        # (make_array_from_process_local_data on multi-host).  Direct
        # jax.device_put(loader.device_context()) would place the local
        # block unsharded and break the local-address contract — always
        # place through here (Workflow.initialize does).
        return {"pool": parallel.shard_batch(self.device_context()["pool"])}

    def _shard_map_pre(self, per_shard_pre):
        """Wrap a per-shard ``pre(payload, pool_block) -> batch`` in a
        shard_map over the data axis (payload rows and pool rows both
        local; the preproc never leaves the device)."""
        import jax
        from jax.sharding import PartitionSpec as P

        from znicz_tpu.parallel.mesh import DATA_AXIS

        mesh = self._mesh
        spec = P(DATA_AXIS)

        def pre(payload, ctx):
            return jax.shard_map(
                per_shard_pre,
                mesh=mesh,
                in_specs=(spec, spec),
                out_specs=spec,
            )(payload, ctx["pool"])

        return pre
