"""The main path's Pallas kernels, compiled for the chip without the chip.

The TPU compiler is installed beside the CPU backend and compiles for a
DESCRIBED ``v5e:2x2`` topology (no device attached), so what Mosaic would
refuse on the chip — a slice off the tiling, too much VMEM, an op with no
lowering — fails here, in tier-1, at no chip time.  Shapes are the ones
``chip_smoke.py`` and the model zoo run: flash attention at the mid LM's
``[8, 2048, 8, 64|128]``, LRN at AlexNet's two normalised activations, the
SOM step and RBM CD-1 at the MNIST zoo sizes, the latent decode attention
at ``axk1-ep16``'s pool and three rungs of the engine's decode window.  Nothing executes: a compile
that passes is not a chip run.

Interpret mode is steered off IN THE TEST (``backend.pallas_interpret``):
``jax.default_backend()`` still says cpu here.  The compiles run at jax's
default matmul precision — what the program uses — not the ``highest`` the
golden tests set in conftest.
"""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else it logs under /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from znicz_tpu.core import backend
from znicz_tpu.ops import kohonen as kh, normalization
from znicz_tpu.ops.pallas import kohonen as pallas_kh, rbm as pallas_rbm
from znicz_tpu.ops.pallas.attention import flash_attention
from znicz_tpu.ops.pallas.latent_attention import latent_decode_attention


@pytest.fixture(scope="module")
def chip():
    """Sharding on one described v5e chip; skips where the TPU compiler
    cannot describe the topology."""
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as exc:  # no libtpu / unknown topology: nothing to test
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {exc}")
    return SingleDeviceSharding(topo.devices[0])


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
