"""The paged K/V pool's stored layout, read from the chip's compiler
without the chip.

The serving programs are lowered on ``ShapeDtypeStruct``s placed on one
device of a DESCRIBED ``v5e:2x2`` topology (no weights drawn, nothing
executes) at ``lm-serve-steady``'s geometry: 768 x 12 layers x 12 heads,
vocabulary 50257, 32 slots, block 32, 1025 blocks, chunk 8, greedy.  What
is asserted is static: the pools as :func:`init_paged_kv` builds them
(``[n_blocks, block_size, H*hd]``: a minor ``[32, 768]`` fills (8, 128)
tiles exactly) cost their logical bytes as arguments, and no program moves
a whole pool or a whole gathered window into another tiling.

What the compiler did before (PERF.md section 6, PR 25).  Heads split in
storage (``[.., 12, 64]`` fits no tile): the pools padded by an eighth as
arguments, 96 pool- or window-sized ``copy`` ops in the decode chunk (48
of them in the loop's body), 48 in every prefill call, 120 in the verify
program, 7.55 GB of temporaries in the decode chunk.  Heads merged in
storage but split in the gathered window: no copy of a pool, but a
``reshape`` kernel of every window in every decode step (24, each writing
the window at 2.7 times its size) and 0.64 GB of temporaries.  Heads merged
in storage and the window read through token-index gathers, with the
ordinary per-head einsum: 48 ``copy`` ops of a window in every decode
step.  Heads split on the query side, the window read as stored
(:func:`ops.attention.paged_attention`): none, and 0.39 GB.

Compiles run at jax's default matmul precision (what the program uses),
3-5 s each.  A compile that passes is not a chip run.
"""

import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else it logs under /tmp

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from znicz_tpu.services import engine
from znicz_tpu.workflow.generate import init_paged_kv

D, LAYERS, HEADS, VOCAB, D_FF, T_MAX = 768, 12, 12, 50257, 3072, 1024
SLOTS, BLOCK, CHUNK, VERIFY_WIDTH = 32, 32, 8, 4
WINDOW = T_MAX // BLOCK  # the widest decode rung: every row at T_MAX
N_BLOCKS = SLOTS * WINDOW + 1
GB = 1e9

SAMPLING = dict(greedy=True, top_k=0, nucleus=False)
TOWER = dict(n_heads=HEADS, block_size=BLOCK, moe_top_k=1, moe_dispatch="dense")


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


def _lm_shapes(spec):
    """``init_lm_params``'s tree at the cell's sizes, as shapes."""
    block = {
        "ln1_scale": (D,), "ln1_bias": (D,), "ln2_scale": (D,),
        "ln2_bias": (D,), "w_up": (D, D_FF), "up_bias": (D_FF,),
        "w_down": (D_FF, D), "down_bias": (D,),
        "wq": (D, D), "wk": (D, D), "wv": (D, D), "wo": (D, D),
    }
    tree = (
        [{"embed": (VOCAB, D), "pos": (T_MAX, D)}]
        + [dict(block) for _ in range(LAYERS)]
        + [{"head": (D, VOCAB)}]
    )
    return jax.tree.map(
        lambda s: spec(s, jnp.float32), tree,
        is_leaf=lambda x: isinstance(x, tuple),
    )


def _lower(program, chip):
    """The program lowered as ``PagedDecodeEngine`` calls it.  Returns
    (lowered, logical bytes of the pools, of the other arguments)."""

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    i32, f32 = jnp.int32, jnp.float32
    params = _lm_shapes(spec)
    # the engine's own pools: this test follows whatever layout it builds
    pools = jax.tree.map(
        lambda a: spec(a.shape, a.dtype),
        jax.eval_shape(
            lambda: init_paged_kv(params, N_BLOCKS, BLOCK)
        ),
    )
    tables = spec((SLOTS, WINDOW), i32)
    rows_i32 = spec((SLOTS,), i32)
    done = spec((SLOTS,), jnp.bool_)
    scalar_i32, scalar_f32 = spec((), i32), spec((), f32)
    key = spec((2,), jnp.uint32)
    if program == "decode_chunk":
        args = (
            params, pools, tables, rows_i32, rows_i32, rows_i32, done,
            rows_i32, scalar_f32, scalar_f32, key,
        )
        lowered = engine._paged_decode_chunk.lower(
            *args, chunk=CHUNK, t_max=T_MAX, eos_id=0, **SAMPLING, **TOWER
        )
    elif program == "prefill":
        args = (
            params, pools, spec((WINDOW,), i32), spec((1, BLOCK), i32),
            scalar_i32, spec((1,), i32), scalar_i32, scalar_f32, scalar_f32,
            key,
        )
        lowered = engine._paged_prefill_prog.lower(*args, **SAMPLING, **TOWER)
    elif program == "verify":
        args = (
            params, pools, tables, spec((SLOTS, VERIFY_WIDTH), i32),
            rows_i32, rows_i32, done, rows_i32, rows_i32, scalar_f32,
            scalar_f32, key,
        )
        lowered = engine._paged_verify_prog.lower(
            *args, width=VERIFY_WIDTH, **SAMPLING, **TOWER
        )
    else:
        args = (pools, scalar_i32, scalar_i32)
        lowered = engine._cow_copy_prog.lower(*args)

    def nbytes(tree):
        return sum(
            int(np.prod(a.shape)) * np.dtype(a.dtype).itemsize
            for a in jax.tree.leaves(tree)
        )

    return lowered, nbytes(pools), nbytes(args) - nbytes(pools)


_OP = re.compile(r"= \w+\[([\d,]+)\]\S* (copy|reshape|transpose)\(")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%([\w.\-]+) \(", re.M)


def _relayouts(text, *element_counts):
    """``"<op> <dims>"`` of every op that moves a whole pool or a whole
    gathered window into another tiling: a ``copy`` anywhere (a fusion of
    one is still a pass over HBM), a ``reshape`` or ``transpose`` that is a
    kernel of its own (inside a fusion they are index arithmetic)."""
    fused = set(re.findall(r"calls=%([\w.\-]+)", text))
    found = []
    heads = list(_COMPUTATION.finditer(text))
    for head, nxt in zip(heads, heads[1:] + [None]):
        body = text[head.end(): nxt.start() if nxt else len(text)]
        for dims, op in _OP.findall(body):
            if op != "copy" and head.group(1) in fused:
                continue
            if int(np.prod([int(d) for d in dims.split(",")])) in element_counts:
                found.append(f"{op} {dims}")
    return found


# temporaries allowed, GB.  The compiler's counts: decode chunk 0.39
# (7.55 with heads split in storage), prefill 0.02 (0.27), verify 0.03
# (0.50), copy-on-write 0
TEMP_LIMIT_GB = {
    "decode_chunk": 1.0, "prefill": 0.1, "verify": 1.0, "cow_copy": 0.01,
}


@pytest.mark.parametrize("program", list(TEMP_LIMIT_GB))
def test_no_program_retiles_a_pool(chip, program):
    assert not jax.config.jax_enable_compilation_cache  # see test_chip_compile
    with jax.default_matmul_precision("default"):
        lowered, pool_bytes, other_bytes = _lower(program, chip)
        compiled = lowered.compile()
    pool_elements = N_BLOCKS * BLOCK * D
    window_elements = SLOTS * WINDOW * BLOCK * D
    moved = _relayouts(compiled.as_text(), pool_elements, window_elements)
    assert not moved, (
        f"{program} re-tiles a whole K/V pool or gathered window "
        f"{len(moved)} times: {sorted(set(moved))}"
    )
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < TEMP_LIMIT_GB[program] * GB, (
        f"{program} holds {mem.temp_size_in_bytes / GB:.2f} GB of temporaries"
    )
    # 24 pools of 1025 x 32 x 768 f32 are 2.42 GB; the other arguments
    # (0.65 GB of weights where there are any) pad by under 0.1 %
    assert pool_bytes == 2 * LAYERS * pool_elements * 4
    padding = mem.argument_size_in_bytes - pool_bytes - other_bytes
    assert 0 <= padding < 0.002 * (pool_bytes + other_bytes), (
        f"{program}: arguments take {mem.argument_size_in_bytes / GB:.3f} GB "
        f"where pools + the rest are {pool_bytes / GB:.3f} + "
        f"{other_bytes / GB:.3f} GB"
    )
