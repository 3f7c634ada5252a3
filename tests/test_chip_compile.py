"""The main path's Pallas kernels, compiled for the chip without the chip.

The TPU compiler is installed beside the CPU backend and compiles for a
DESCRIBED ``v5e:2x2`` topology (no device attached), so what Mosaic would
refuse on the chip — a slice off the tiling, too much VMEM, an op with no
lowering — fails here, in tier-1, at no chip time.  Shapes are the ones
``chip_smoke.py`` and the model zoo run: flash attention at the mid LM's
``[8, 2048, 8, 64|128]``, LRN at AlexNet's two normalised activations,
AlexNet's two conv stages with their fused tails at batch 1024, the
SOM step and RBM CD-1 at the MNIST zoo sizes, the latent decode attention
at ``axk1-ep16``'s pool and three rungs of the engine's decode window.  Nothing executes: a compile
that passes is not a chip run.

Interpret mode is steered off IN THE TEST (``backend.pallas_interpret``):
``jax.default_backend()`` still says cpu here.  The compiles run at jax's
default matmul precision — what the program uses — not the ``highest`` the
golden tests set in conftest.
"""

import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else it logs under /tmp

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from znicz_tpu.core import backend
from znicz_tpu.ops import conv, kohonen as kh, normalization
from znicz_tpu.ops.pallas import kohonen as pallas_kh, rbm as pallas_rbm
from znicz_tpu.ops.pallas.attention import flash_attention
from znicz_tpu.ops.pallas.latent_attention import (
    latent_decode_attention,
    shared_run_decode_attention,
)


@pytest.fixture(scope="module")
def topology():
    """A described v5e 2x2; skips where the TPU compiler cannot describe
    it."""
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as exc:  # no libtpu / unknown topology: nothing to test
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {exc}")


@pytest.fixture(scope="module")
def chip(topology):
    """Sharding on one described v5e chip."""
    return SingleDeviceSharding(topology.devices[0])


@pytest.fixture(autouse=True)
def _compiled_for_the_chip(monkeypatch):
    """Kernels compile (not interpret), at the program's own precision.
    The persistent cache must be off around them — conftest switches it
    off for the whole suite: an entry written for a described chip cannot
    be read back without one and only warns on the next run."""
    assert not jax.config.jax_enable_compilation_cache
    monkeypatch.setattr(backend, "pallas_interpret", lambda: False)
    with jax.default_matmul_precision("default"):
        yield


def _compile(fn, *shapes):
    text = jax.jit(fn).lower(*shapes).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def _grad_of(fn, n_args):
    return jax.grad(
        lambda *a: jnp.sum(fn(*a).astype(jnp.float32)),
        argnums=tuple(range(n_args)),
    )


@pytest.mark.parametrize("direction", ["fwd", "grad"])
@pytest.mark.parametrize(
    "dtype,head_dim",
    [(jnp.float32, 64), (jnp.bfloat16, 64), (jnp.bfloat16, 128)],
)
def test_flash_attention_compiles(chip, dtype, head_dim, direction):
    qkv = jax.ShapeDtypeStruct((8, 2048, 8, head_dim), dtype, sharding=chip)

    def fn(q, k, v):
        return flash_attention(q, k, v, causal=True)

    _compile(fn if direction == "fwd" else _grad_of(fn, 3), qkv, qkv, qkv)


@pytest.mark.parametrize("direction", ["fwd", "grad"])
@pytest.mark.parametrize(
    "shape,dtype",
    [((1024, 27, 27, 96), jnp.float32), ((1024, 13, 13, 256), jnp.bfloat16)],
)
def test_lrn_compiles(chip, shape, dtype, direction):
    x = jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    def fn(x):
        return normalization.lrn(x, impl="pallas")

    _compile(fn if direction == "fwd" else _grad_of(fn, 1), x)


@pytest.mark.parametrize("direction", ["fwd", "grad"])
@pytest.mark.parametrize(
    "x_shape,w_shape,conv_kw,stage,view",
    [
        ((1024, 227, 227, 3), (11, 11, 3, 96), dict(sliding=(4, 4)),
         "55,55,96,1024", "3025,96,1024"),
        ((1024, 27, 27, 96), (5, 5, 96, 256), dict(padding=(2, 2, 2, 2)),
         "27,27,1024,256", "729,1024,256"),
    ],
    ids=["alexnet-conv1", "alexnet-conv2"],
)
def test_fused_conv_stage_compiles_without_a_copy(
    chip, monkeypatch, x_shape, w_shape, conv_kw, stage, view, direction
):
    """AlexNet's two conv -> norm stages at batch 1024, bf16: the conv is
    asked for the order the compiler lays its output out in anyway (conv1:
    the batch on the lanes; conv2: the channels), so the tail's kernels take
    bitcasts and no stage-sized tensor is copied or transposed beside them."""
    monkeypatch.setattr(backend, "on_tpu", lambda: True)
    x = jax.ShapeDtypeStruct(x_shape, jnp.bfloat16, sharding=chip)
    w = jax.ShapeDtypeStruct(w_shape, jnp.bfloat16, sharding=chip)
    b = jax.ShapeDtypeStruct(w_shape[-1:], jnp.bfloat16, sharding=chip)

    def fn(x, w, b):
        return conv.apply_lrn(
            {"weights": w, "bias": b}, x, activation="relu", n=5,
            alpha=1e-4, beta=0.75, k=2.0, **conv_kw,
        )

    text = _compile(fn if direction == "fwd" else _grad_of(fn, 3), x, w, b)
    kernels = [
        line for line in text.splitlines()
        if "tpu_custom_call" in line and "custom-call(" in line
    ]
    # the gradient alone needs the backward kernel alone: it recomputes
    assert len(kernels) == 1, kernels
    assert f"bf16[{view}]" in kernels[0], (view, kernels)
    moved = [
        line.strip()[:160] for line in text.splitlines()
        if re.search(
            r"= bf16\[(%s|%s|1024,\d+,\d+,(96|256))\]\S* (copy|transpose)\("
            % (stage, view), line,
        )
    ]
    assert not moved, moved


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize(
    "shape,channel_axis", [((55, 55, 96, 1024), 2), ((27, 27, 1024, 256), 3)]
)
def test_fused_tail_compiles_at_highest_matmul_precision(
    chip, monkeypatch, shape, channel_axis, dtype
):
    """The golden tests and chip_smoke's serve phase run under
    ``jax_default_matmul_precision=highest``: the kernels' bf16 band
    products name their own precision, or Mosaic refuses them there
    ("Bad lhs type": my chip run, PR 37)."""
    monkeypatch.setattr(backend, "on_tpu", lambda: True)
    y = jax.ShapeDtypeStruct(shape, dtype, sharding=chip)
    b = jax.ShapeDtypeStruct((shape[channel_axis],), jnp.float32, sharding=chip)

    def fn(y, b):
        return normalization.act_lrn(y, b, channel_axis=channel_axis)

    with jax.default_matmul_precision("highest"):
        _compile(fn, y, b)
        _compile(_grad_of(fn, 2), y, b)


def test_fused_conv_stage_runs_per_shard_on_four_chips(topology, monkeypatch):
    """conv1's stage at a global batch of 4096 over the four chips, traced
    under ``DataParallel.scope()`` as the workflow traces a step: each chip's
    kernel takes its own 1024 images (no partitioning rule exists for a
    Pallas call, and ``custom_partitioning`` has no hook in libtpu: the op
    is a ``shard_map`` region), nothing is gathered, and the weights'
    gradient is what is all-reduced."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from znicz_tpu.parallel import DataParallel
    from znicz_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS

    monkeypatch.setattr(backend, "on_tpu", lambda: True)
    mesh = Mesh(
        np.array(topology.devices).reshape(4, 1), (DATA_AXIS, MODEL_AXIS)
    )
    policy = DataParallel(mesh)
    whole, split = NamedSharding(mesh, P()), NamedSharding(mesh, P(DATA_AXIS))
    x = jax.ShapeDtypeStruct((4096, 227, 227, 3), jnp.bfloat16, sharding=split)
    w = jax.ShapeDtypeStruct((11, 11, 3, 96), jnp.bfloat16, sharding=whole)
    b = jax.ShapeDtypeStruct((96,), jnp.bfloat16, sharding=whole)

    def fn(x, w, b):
        with policy.scope():
            return conv.apply_lrn(
                {"weights": w, "bias": b}, x, activation="relu",
                sliding=(4, 4), n=5, alpha=1e-4, beta=0.75, k=2.0,
            )

    text = _compile(_grad_of(fn, 3), x, w, b)
    kernels = [
        line for line in text.splitlines()
        if "tpu_custom_call" in line and "custom-call(" in line
    ]
    assert len(kernels) == 1 and "bf16[3025,96,1024]" in kernels[0], kernels
    assert "all-gather" not in text
    assert "all-reduce" in text


@pytest.mark.parametrize("side,batch", [(8, 100), (16, 1000)])
def test_som_step_compiles(chip, side, batch):
    coords = kh.grid_coords(side, side)
    w = jax.ShapeDtypeStruct((side * side, 784), jnp.float32, sharding=chip)
    x = jax.ShapeDtypeStruct((batch, 784), jnp.float32, sharding=chip)
    mask = jax.ShapeDtypeStruct((batch,), jnp.float32, sharding=chip)

    def fn(w, x, mask):
        return pallas_kh.train_step(
            {"weights": w}, x, coords, learning_rate=0.5, sigma=1.5,
            mask=mask,
        )

    _compile(fn, w, x, mask)


@pytest.mark.parametrize("batch", [100, 128])
def test_rbm_cd1_compiles_with_the_hardware_prng(chip, batch):
    def f32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=chip)

    step = jax.ShapeDtypeStruct((), jnp.int32, sharding=chip)

    def fn(w, vb, hb, v0, step):
        return pallas_rbm.cd_step(
            {"weights": w, "vbias": vb, "hbias": hb}, v0, step,
            learning_rate=0.1, cd_k=1,
        )

    text = _compile(
        fn, f32(784, 128), f32(784), f32(128), f32(batch, 784), step
    )
    # interpret mode's host-made uniforms must be gone: the chain samples
    # with the chip's PRNG, so no threefry program rides along
    assert "threefry" not in text


@pytest.mark.parametrize(
    "table_width, bounded", [(1, False), (128, False), (34, True)],
    ids=["global-rung-1", "global-rung-128", "window-ring"],
)
def test_gqa_decode_attention_compiles(chip, table_width, bounded):
    # smallthinker-21b-l8 as served: 64 slots, 28 query heads padded to 32
    # rows, [v, k] rows of 1,024 bfloat16 lanes in blocks of 128; a global
    # layer walks its rung, a window layer its ring turned to start at the
    # window's first block, with the window's first key as a lower bound
    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    def fn(q_row, pool, tables, lengths, starts):
        return latent_decode_attention(
            q_row, pool, tables, lengths, scale=0.0884, d_out=512,
            starts=starts if bounded else None,
        )

    _compile(
        fn, spec((64, 32, 1024), jnp.bfloat16),
        spec((1600, 128, 1024), jnp.bfloat16),
        spec((64, table_width), jnp.int32), spec((64,), jnp.int32),
        spec((64,), jnp.int32),
    )


@pytest.mark.parametrize(
    "slots, heads, lanes, table_width, blocks, q_from",
    [
        (48, 48, 2048, 176, 1280, 0), (48, 48, 2048, 176, 1280, 1024),
        (48, 48, 2048, 4, 1280, 0),
        (64, 32, 1024, 128, 1600, 0), (64, 32, 1024, 128, 1600, 512),
    ],
    ids=[
        "laguna", "laguna-key-lanes", "laguna-rung-4", "smallthinker",
        "smallthinker-key-lanes",
    ],
)
def test_shared_run_decode_attention_compiles(
    chip, slots, heads, lanes, table_width, blocks, q_from
):
    # a global layer's decode step of the two grouped-query window towers
    # as served (laguna-xs2-stage1: 48 slots, 48 query rows, [v, k] rows of
    # 2,048 bfloat16 lanes, a table of 176, a pool of 1,280 blocks;
    # smallthinker-21b-l8: 64 slots, 28 rows padded to 32, 1,024 lanes):
    # the plan (scalar loops over the table and the lengths in SMEM), the
    # shared pass (a tile's 8 x heads stacked query rows, its own VMEM
    # limit) and the own pass that goes on from the shared pass's
    # statistics, three kernels
    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    def fn(q_row, pool, tables, lengths):
        return shared_run_decode_attention(
            q_row, pool, tables, lengths, scale=0.0884, d_out=lanes // 2,
            q_from=q_from,
        )

    text = _compile(
        fn, spec((slots, heads, lanes), jnp.bfloat16),
        spec((blocks, 128, lanes), jnp.bfloat16),
        spec((slots, table_width), jnp.int32), spec((slots,), jnp.int32),
    )
    assert len(re.findall(r"custom_call_target=\"tpu_custom_call\"", text)) == 3


@pytest.mark.parametrize("window", [1, 4, 96])
def test_latent_decode_attention_compiles(chip, window):
    # axk1-ep16 as served: 128 slots, 64 heads, 4,096 blocks of 128 rows of
    # 640 bfloat16; the engine's decode window doubles from 1 block to 96
    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    def fn(q_row, pool, tables, lengths):
        return latent_decode_attention(
            q_row, pool, tables, lengths, scale=0.1147, d_out=512
        )

    _compile(
        fn, spec((128, 64, 640), jnp.bfloat16),
        spec((4096, 128, 640), jnp.bfloat16), spec((128, window), jnp.int32),
        spec((128,), jnp.int32),
    )


@pytest.mark.parametrize("table_width", [32, 264])
def test_index_decode_scores_compiles(chip, table_width):
    # dots3-ep16-l5 as served: 64 slots, 64 indexer heads of 128, 8,192
    # blocks of 128 indexer keys of 128 bfloat16; the engine's decode window
    # doubles from 32 blocks (the shortest prompt's rung) to 264
    from znicz_tpu.ops.pallas.sparse_index import index_decode_scores

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    _compile(
        index_decode_scores, spec((64, 64, 128), jnp.bfloat16),
        spec((64, 64), jnp.float32), spec((8192, 128, 128), jnp.bfloat16),
        spec((64, table_width), jnp.int32), spec((64,), jnp.int32),
    )


def test_window_latent_decode_attention_compiles(chip):
    # dots3-ep16-l5's window layers as served: 64 slots, 64 heads, 512
    # blocks of 128 rows of 1,152 bfloat16 lanes, a ring of 6 turned to
    # start at the window's first block, the window's first key a lower
    # bound; the weighted sum over the 1,024 lanes of the latent
    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    def fn(q_row, pool, tables, lengths, starts):
        return latent_decode_attention(
            q_row, pool, tables, lengths, scale=0.0625, d_out=1024,
            starts=starts,
        )

    _compile(
        fn, spec((64, 64, 1152), jnp.bfloat16),
        spec((512, 128, 1152), jnp.bfloat16), spec((64, 6), jnp.int32),
        spec((64,), jnp.int32), spec((64,), jnp.int32),
    )


@pytest.mark.parametrize("table_width", [32, 264])
def test_latent_decode_attention_under_a_keep_mask_compiles(chip, table_width):
    # dots3-ep16-l5's full layers as served: 64 slots, 128 heads, 8,192
    # blocks of 128 rows of 640 bfloat16, and which of a row's keys its
    # query keeps (2,048 of them) as a mask over the table's positions
    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    def fn(q_row, pool, tables, lengths, keep):
        return latent_decode_attention(
            q_row, pool, tables, lengths, scale=0.0722, d_out=512, keep=keep
        )

    _compile(
        fn, spec((64, 128, 640), jnp.bfloat16),
        spec((8192, 128, 640), jnp.bfloat16),
        spec((64, table_width), jnp.int32), spec((64,), jnp.int32),
        spec((64, table_width * 128), jnp.bool_),
    )
