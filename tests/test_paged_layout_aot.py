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
    scalar_i32, scalar_f32 = spec((), i32), spec((), f32)
    key = spec((2,), jnp.uint32)
    if program == "decode_chunk":
        args = (
            params, pools, tables, spec((5, SLOTS), i32), scalar_f32,
            scalar_f32, key,
        )
        lowered = engine._paged_decode_chunk.lower(
            *args, chunk=CHUNK, t_max=T_MAX, eos_id=0, **SAMPLING, **TOWER
        )
    elif program == "prefill":
        args = (
            params, pools, spec((WINDOW,), i32), spec((1, T_MAX), i32),
            spec((3,), i32), scalar_f32, scalar_f32, key,
        )
        lowered = engine._paged_prefill_prog.lower(*args, **SAMPLING, **TOWER)
    elif program == "verify":
        args = (
            params, pools, tables, spec((SLOTS, VERIFY_WIDTH + 5), i32),
            scalar_f32, scalar_f32, key,
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


# -- the latent pool of a LatentMoEModel at axk1-ep16's geometry -----------
#
# 6 layers (one dense) at hidden 7168, 64 heads, latent 512 + rope 64, 12
# of 192 experts of width 2048 held, vocabulary slice 20480, bfloat16; 128
# slots, block 128, 4096 blocks, the widest decode rung (96 blocks).  What
# the compiler said of a 576-wide row (my AOT compiles, PR 26): it keeps
# such a pool tokens-minor ({1,2,0}), every program then copies all six
# pools into the layout it gathers from, and the decode program needs
# 17.0 GiB of the chip's 15.75.  A 640-wide row (latent, rope, zeros) is
# stored as computed on: no copy of a pool, 2.74 GB of temporaries beside
# 12.36 GB of arguments.

AX = dict(d=7168, heads=64, d_q=1536, d_c=512, d_n=128, d_r=64, d_v=128,
          f_dense=18432, f_exp=2048, held=12, published=192, vocab=20480,
          layers=6, slots=128, block=128, n_blocks=4096, window=96)


def _axk1_model():
    from znicz_tpu.workflow.latent_lm import LatentMoEModel

    return LatentMoEModel(
        n_heads=AX["heads"], kv_lora_rank=AX["d_c"],
        qk_nope_head_dim=AX["d_n"], qk_rope_head_dim=AX["d_r"], top_k=8,
        routed_scaling_factor=2.5, first_expert=36, max_positions=131072,
        rope_factor=32.0,
    )


def _axk1_params(spec):
    a = AX
    bf, f32 = jnp.bfloat16, jnp.float32
    d, h = a["d"], a["heads"]

    def block(dense):
        leaves = {
            "attn_norm": ((d,), f32), "wq_a": ((d, a["d_q"]), bf),
            "q_norm": ((a["d_q"],), f32),
            "wq_b_nope": ((a["d_q"], h * a["d_n"]), bf),
            "wq_b_rope": ((a["d_q"], h * a["d_r"]), bf),
            "wkv_a": ((d, a["d_c"] + a["d_r"]), bf),
            "kv_norm": ((a["d_c"],), f32),
            "wk_b": ((a["d_c"], h * a["d_n"]), bf),
            "wv_b": ((a["d_c"], h * a["d_v"]), bf),
            "wo": ((h * a["d_v"], d), bf), "ffn_norm": ((d,), f32),
        }
        if dense:
            leaves.update(
                w_gate=((d, a["f_dense"]), bf), w_up=((d, a["f_dense"]), bf),
                w_down=((a["f_dense"], d), bf),
            )
        else:
            f, g = a["f_exp"], a["held"]
            leaves.update(
                router=((d, a["published"]), bf),
                experts_gate=((g, d, f), bf), experts_up=((g, d, f), bf),
                experts_down=((g, f, d), bf), shared_gate=((d, f), bf),
                shared_up=((d, f), bf), shared_down=((f, d), bf),
            )
        return {k: spec(*v) for k, v in leaves.items()}

    return (
        [{"embed": spec((a["vocab"], d), bf)}]
        + [block(i == 0) for i in range(a["layers"])]
        + [{"final_norm": spec((d,), f32), "head": spec((d, a["vocab"]), bf)}]
    )


AXK1_TEMP_LIMIT_GB = {"decode_chunk": 1.0, "prefill": 0.6, "cow_copy": 0.01}
_SHAPE = re.compile(r"= \(?\w+\[([\d,]+)\]")


@pytest.mark.parametrize("program", list(AXK1_TEMP_LIMIT_GB))
def test_the_latent_pool_is_stored_as_it_is_computed_on(chip, program, monkeypatch):
    from znicz_tpu.core import backend

    # the grouped expert product picks its TPU kernel by the backend it
    # finds; this process computes on the CPU and compiles for the chip
    monkeypatch.setattr(backend, "on_tpu", lambda: True)
    monkeypatch.setattr(backend, "pallas_interpret", lambda: False)
    a, model = AX, _axk1_model()

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    i32, f32 = jnp.int32, jnp.float32
    params = _axk1_params(spec)
    pools = jax.tree.map(
        lambda p: spec(p.shape, p.dtype),
        jax.eval_shape(
            lambda: model.init_pools(params, a["n_blocks"], a["block"])
        ),
    )
    assert pools[0]["kv"].shape == (a["n_blocks"], a["block"], 640)
    state, scalar_i32 = spec((5, a["slots"]), i32), spec((), i32)
    scalar_f32, key = spec((), f32), spec((2,), jnp.uint32)
    tower = dict(
        n_heads=a["heads"], block_size=a["block"], moe_top_k=1,
        moe_dispatch="dense", model=model,
    )
    with jax.default_matmul_precision("default"):
        if program == "decode_chunk":
            lowered = engine._paged_decode_chunk.lower(
                params, pools, spec((a["slots"], a["window"]), i32), state,
                scalar_f32, scalar_f32, key, chunk=CHUNK, t_max=12288,
                eos_id=0, **SAMPLING, **tower,
            )
        elif program == "prefill":
            lowered = engine._paged_prefill_prog.lower(
                params, pools, spec((a["window"],), i32),
                spec((1, 12288), i32), spec((3,), i32),
                scalar_f32, scalar_f32, key, **SAMPLING, **tower,
            )
        else:
            lowered = engine._cow_copy_prog.lower(pools, scalar_i32, scalar_i32)
        compiled = lowered.compile()  # raises where the chip would refuse it
    text = compiled.as_text()
    pool_elements = a["n_blocks"] * a["block"] * 640
    moved = [m for m in _relayouts(text, pool_elements) if m.startswith("copy")]
    assert not moved, f"{program} copies a whole latent pool: {sorted(set(moved))}"
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < AXK1_TEMP_LIMIT_GB[program] * GB, (
        f"{program} holds {mem.temp_size_in_bytes / GB:.2f} GB of temporaries"
    )
    if program != "cow_copy":
        # weights 8.33 GB + pools 4.03 GB + temporaries fit 15.75 GiB, and
        # the three grouped products of each routed layer are the kernel
        assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75 * 2**30
        assert text.count("tpu_custom_call") >= 3 * (a["layers"] - 1)
    if program == "decode_chunk":
        # every layer's absorbed attention is a kernel too, and nothing
        # the program computes is as large as a gathered window
        assert text.count("tpu_custom_call") >= 3 * (a["layers"] - 1) + a["layers"]
        window_elements = a["slots"] * a["window"] * a["block"] * 640
        gathered = sorted(
            {
                dims for dims in _SHAPE.findall(text)
                if window_elements
                <= int(np.prod([int(d) for d in dims.split(",")]))
                < pool_elements
            }
        )
        assert not gathered, f"the decode chunk still holds a window: {gathered}"


# -- the two kinds of pool of a WindowGQAMoEModel at smallthinker-21b-l8's --
#
# 8 layers (global, window, window, window, twice) at hidden 2560, 28 query
# heads of 128 over 4 K/V heads, 64 experts of width 768 all held, the whole
# vocabulary of 151936, bfloat16; 64 slots, block 128, 4096 global and 1600
# window blocks of [v, k] rows (1024 lanes: whole tiles), the widest global
# rung (128 blocks) beside the window kind's ring of 34.  My AOT compiles,
# PR 31: 12.60 GB of arguments; the decode chunk holds 0.19 GB of
# temporaries (0.76 while the six window layers gathered their rings: one
# ring of 64 x 4352 x 1024 bf16 is 0.57), the prefill chunk 0.28; 24
# grouped products, and in a decode chunk every layer's attention as the
# kernel that reads the pool in place.

ST = dict(d=2560, heads=28, kv_heads=4, dim=128, f=768, experts=64,
          vocab=151936, layers=8, slots=64, block=128, rung=128, ring=34,
          n_blocks={"global": 4096, "window": 1600})


def _smallthinker_model():
    from znicz_tpu.workflow.window_lm import WindowGQAMoEModel

    return WindowGQAMoEModel(
        n_heads=ST["heads"], n_kv_heads=ST["kv_heads"], head_dim=ST["dim"],
        top_k=6, window=4096, windowed=(False, True, True, True) * 2,
        max_positions=16384, rope_theta=1.5e6,
    )


def _smallthinker_params(spec):
    a, bf, f32 = ST, jnp.bfloat16, jnp.float32
    d, q, kv = a["d"], a["heads"] * a["dim"], a["kv_heads"] * a["dim"]
    e, f = a["experts"], a["f"]
    leaves = {
        "attn_norm": ((d,), f32), "wq": ((d, q), bf), "wk": ((d, kv), bf),
        "wv": ((d, kv), bf), "wo": ((q, d), bf), "ffn_norm": ((d,), f32),
        "router": ((d, e), bf), "experts_gate": ((e, d, f), bf),
        "experts_up": ((e, d, f), bf), "experts_down": ((e, f, d), bf),
    }
    return (
        [{"embed": spec((a["vocab"], d), bf)}]
        + [{k: spec(*v) for k, v in leaves.items()} for _ in range(a["layers"])]
        + [{"final_norm": spec((d,), f32), "head": spec((d, a["vocab"]), bf)}]
    )


SMALLTHINKER_TEMP_LIMIT_GB = {"decode_chunk": 0.4, "prefill": 0.5}


@pytest.mark.parametrize("program", list(SMALLTHINKER_TEMP_LIMIT_GB))
def test_the_two_kinds_of_pool_are_stored_as_they_are_computed_on(
    chip, program, monkeypatch
):
    from znicz_tpu.core import backend

    monkeypatch.setattr(backend, "on_tpu", lambda: True)
    monkeypatch.setattr(backend, "pallas_interpret", lambda: False)
    a, model = ST, _smallthinker_model()

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    i32, f32 = jnp.int32, jnp.float32
    params = _smallthinker_params(spec)
    pools = jax.tree.map(
        lambda p: spec(p.shape, p.dtype),
        jax.eval_shape(
            lambda: model.init_pools(params, a["n_blocks"], a["block"])
        ),
    )
    assert [p["kv"].shape[0] for p in pools] == [4096, 1600, 1600, 1600] * 2
    assert pools[0]["kv"].shape[1:] == (a["block"], 1024)
    state = spec((5, a["slots"]), i32)
    scalar_f32, key = spec((), f32), spec((2,), jnp.uint32)
    tower = dict(
        n_heads=a["heads"], block_size=a["block"], moe_top_k=1,
        moe_dispatch="dense", model=model,
    )
    with jax.default_matmul_precision("default"):
        if program == "decode_chunk":
            tables = {
                "global": spec((a["slots"], a["rung"]), i32),
                "window": spec((a["slots"], a["ring"]), i32),
            }
            lowered = engine._paged_decode_chunk.lower(
                params, pools, tables, state, scalar_f32,
                scalar_f32, key, chunk=CHUNK, t_max=16384, eos_id=0,
                **SAMPLING, **tower,
            )
        else:
            table = {
                "global": spec((a["rung"],), i32),
                "window": spec((a["ring"],), i32),
            }
            lowered = engine._paged_prefill_prog.lower(
                params, pools, table, spec((1, 16384), i32), spec((3,), i32),
                scalar_f32, scalar_f32, key, **SAMPLING, **tower,
            )
        compiled = lowered.compile()  # raises where the chip would refuse it
    text = compiled.as_text()
    row = a["block"] * 1024
    ring = a["slots"] * a["ring"] * row
    # no pool is copied or re-tiled, no gathered ring is (a slice of one,
    # the value half, is a copy of half of it)
    moved = _relayouts(text, 4096 * row, 1600 * row, ring, ring // 2)
    assert not moved, f"{program} moves a pool or a ring: {sorted(set(moved))}"
    assert not re.search(r"= bf16\[64,4352,512\]\S* (slice|fusion)\(", text)
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < SMALLTHINKER_TEMP_LIMIT_GB[program] * GB, (
        f"{program} holds {mem.temp_size_in_bytes / GB:.2f} GB of temporaries"
    )
    # weights 7.93 GB + pools 4.67 GB + temporaries fit 15.75 GiB; the three
    # grouped products of every layer are the kernel, and in a decode step
    # every layer's attention is one too (no gathered window, no ring)
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75 * 2**30
    # (a global layer's two: the pass over the blocks a tile of rows share
    # and the pass over each row's own)
    kernels = text.count("tpu_custom_call")
    assert kernels >= (4 if program == "decode_chunk" else 3) * a["layers"] + (
        2 if program == "decode_chunk" else 0
    )
    if program == "decode_chunk":
        gathered = (a["slots"] * a["rung"] * row, ring)
        assert not [
            dims for dims in _SHAPE.findall(text)
            if int(np.prod([int(d) for d in dims.split(",")])) in gathered
        ], "the decode chunk gathers a window or a ring"


# -- two kinds of LATENT rows of different widths, at dots3-ep16-l5's ------
#
# 5 layers (full and dense, full, window, window, window) at hidden 5120;
# full layers 128 heads over a latent of 512 with an indexer of 64 heads
# that keeps 2,048 keys, window layers 64 heads over a latent of 1,024
# behind 513 keys; 16 of 256 experts of width 1,536 held, an eighth of the
# vocabulary, bfloat16; 64 slots, block 128, 8,192 global blocks of 640
# lanes of latent rows and 128 of indexer keys beside them, 512 window
# blocks of 1,152 lanes (whole tiles all), the widest global rung (264
# blocks = 33,792 positions) beside the window kind's ring of 6.  With the
# indexer's key in the tail lanes of one 768-lane row the compiler re-laid
# the whole global pool for the indexer's lane-sliced gather: a copy of
# 1.6 GB a full layer in every call of either program (my AOT compiles,
# PR 36); with a pool of its own there is none.

D3 = dict(d=5120, vocab=19008, held=16, experts=256, f=1536, f_dense=13824,
          slots=64, block=128, rung=264, ring=6, top_k=2048,
          n_blocks={"global": 8192, "window": 512})


def _dots3_model():
    from znicz_tpu.workflow.sparse_latent_lm import (
        LatentSizes, SparseLatentMoEModel,
    )

    return SparseLatentMoEModel(
        full=LatentSizes(128, 512, 128, 64, 8e7, 5 ** 0.5, 10 ** 0.5),
        swa=LatentSizes(64, 1024, 192, 64, 5e4, 5 ** 0.5, 5 ** 0.5),
        full_layers=(True, True, False, False, False), window=513,
        index_n_heads=64, index_head_dim=128, index_topk=D3["top_k"],
        top_k=8, routed_scaling_factor=1.0, first_expert=80,
        max_positions=524288,
    )


def _dots3_params(spec):
    a, bf, f32 = D3, jnp.bfloat16, jnp.float32
    d, f, held = a["d"], a["f"], a["held"]

    def attention(heads, d_q, d_c, d_n, full):
        leaves = {
            "attn_norm": ((d,), f32), "wq_a": ((d, d_q), bf),
            "q_norm": ((d_q,), f32), "wq_b_nope": ((d_q, heads * d_n), bf),
            "wq_b_rope": ((d_q, heads * 64), bf), "wkv_a": ((d, d_c + 64), bf),
            "kv_norm": ((d_c,), f32), "wk_b": ((d_c, heads * d_n), bf),
            "wv_b": ((d_c, heads * 128), bf), "wg": ((d, heads), bf),
            "wo": ((heads * 128, d), bf), "ffn_norm": ((d,), f32),
        }
        if full:
            leaves.update(
                wq_idx=((d_q, 64 * 128), bf), wk_idx=((d, 128), bf),
                k_idx_gain=((128,), f32), k_idx_bias=((128,), f32),
                w_idx=((d, 64), bf),
            )
        return leaves

    dense = {"w_gate": ((d, a["f_dense"]), bf), "w_up": ((d, a["f_dense"]), bf),
             "w_down": ((a["f_dense"], d), bf)}
    routed = {
        "router": ((d, a["experts"]), bf), "router_bias": ((a["experts"],), f32),
        "experts_gate": ((held, d, f), bf), "experts_up": ((held, d, f), bf),
        "experts_down": ((held, f, d), bf), "shared_gate": ((d, f), bf),
        "shared_up": ((d, f), bf), "shared_down": ((f, d), bf),
    }
    full = attention(128, 1024, 512, 128, True)
    swa = attention(64, 1024, 1024, 192, False)
    blocks = [{**full, **dense}, {**full, **routed}] + [{**swa, **routed}] * 3
    return (
        [{"embed": spec((a["vocab"], d), bf)}]
        + [{k: spec(*v) for k, v in block.items()} for block in blocks]
        + [{"final_norm": spec((d,), f32), "head": spec((d, a["vocab"]), bf)}]
    )


# what my AOT compiles read, PR 36, in GB of temporaries: see the test
DOTS3_TEMP_LIMIT_GB = {"decode_chunk": 0.7, "prefill": 0.6}  # read: 0.35, 0.27


@pytest.mark.parametrize("program", list(DOTS3_TEMP_LIMIT_GB))
def test_two_widths_of_latent_rows_are_stored_as_they_are_computed_on(
    chip, program, monkeypatch
):
    from znicz_tpu.core import backend

    monkeypatch.setattr(backend, "on_tpu", lambda: True)
    monkeypatch.setattr(backend, "pallas_interpret", lambda: False)
    a, model = D3, _dots3_model()

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    i32, f32 = jnp.int32, jnp.float32
    params = _dots3_params(spec)
    pools = jax.tree.map(
        lambda p: spec(p.shape, p.dtype),
        jax.eval_shape(
            lambda: model.init_pools(params, a["n_blocks"], a["block"])
        ),
    )
    assert [p["kv"].shape for p in pools] == (
        [(8192, 128, 640)] * 2 + [(512, 128, 1152)] * 3
    )
    assert [p["idx"].shape for p in pools[:2]] == [(8192, 128, 128)] * 2
    state = spec((5, a["slots"]), i32)
    scalar_f32, key = spec((), f32), spec((2,), jnp.uint32)
    tower = dict(
        n_heads=128, block_size=a["block"], moe_top_k=1,
        moe_dispatch="dense", model=model,
    )
    with jax.default_matmul_precision("default"):
        if program == "decode_chunk":
            tables = {
                "global": spec((a["slots"], a["rung"]), i32),
                "window": spec((a["slots"], a["ring"]), i32),
            }
            lowered = engine._paged_decode_chunk.lower(
                params, pools, tables, state, scalar_f32,
                scalar_f32, key, chunk=CHUNK, t_max=33792, eos_id=0,
                **SAMPLING, **tower,
            )
        else:
            table = {
                "global": spec((a["rung"],), i32),
                "window": spec((a["ring"],), i32),
            }
            lowered = engine._paged_prefill_prog.lower(
                params, pools, table, spec((1, 33792), i32), spec((3,), i32),
                scalar_f32, scalar_f32, key, **SAMPLING, **tower,
            )
        compiled = lowered.compile()  # raises where the chip would refuse it
    text = compiled.as_text()
    mem = compiled.memory_analysis()
    print(
        f"dots3 {program}: arguments {mem.argument_size_in_bytes / GB:.2f} GB, "
        f"temporaries {mem.temp_size_in_bytes / GB:.2f} GB, "
        f"{text.count('tpu_custom_call')} kernels"
    )
    # neither pool is copied or re-tiled, in or out of either program, and
    # no window of the table's width is gathered from the global pool (a
    # full layer reads its indexer keys and its latent rows a chunk of the
    # table at a time, or in place)
    full_row, swa_row, idx_row = a["block"] * 640, a["block"] * 1152, a["block"] * 128
    window = (1 if program == "prefill" else a["slots"]) * a["rung"] * full_row
    moved = _relayouts(
        text, 8192 * full_row, 8192 * idx_row, 512 * swa_row, window
    )
    assert not moved, f"{program} moves a pool or a window: {sorted(set(moved))}"
    assert not [
        dims for dims in _SHAPE.findall(text)
        if int(np.prod([int(d) for d in dims.split(",")])) == window
    ], f"{program} gathers the table's whole width"
    assert mem.temp_size_in_bytes < DOTS3_TEMP_LIMIT_GB[program] * GB, (
        f"{program} holds {mem.temp_size_in_bytes / GB:.2f} GB of temporaries"
    )
    # weights 5.15 GB + pools 3.67 GB + temporaries fit 15.75 GiB
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75 * 2**30
    # the grouped products of the four routed layers are the kernel, and in
    # a decode step every layer's attention is one too, and the two full
    # layers' indexers
    assert text.count("tpu_custom_call") >= (
        3 * 4 + (3 + 2 + 2 if program == "decode_chunk" else 0)
    )


# -- [v, k] rows with the indexer's keys beside them, at keye-vl2-30b-a3b-l6's
#
# 6 layers alike at hidden 2,048: 32 query heads over 4 K/V heads of 128,
# an indexer of 16 heads of 64 that keeps 2,048 keys in EVERY layer, 128
# experts of width 768 all held, the whole vocabulary, bfloat16; 64 slots,
# block 128, 2,048 blocks of 1,024 lanes of [v, k] rows and 128 of indexer
# keys beside them, the one rung (544 blocks = 69,632 positions).  A decode
# step attends the kept rows inside one kernel a layer that fetches them
# from the pool where it lies (ops/pallas/kept_rows_attention.py, picked by
# ``backend.on_tpu()``, which the test patches): the fetched rows ([64,
# 2,048, 1,024], 0.27 GB a layer as the gathered form wrote them out, PR 41)
# exist nowhere in the program, and nothing is gathered at the table's width.

KEYE = dict(d=2048, vocab=151936, experts=128, f=768, slots=64, block=128,
            rung=544, top_k=2048, n_blocks={"global": 2048})
# read: 0.39, 0.14 (0.66 in the decode chunk while the kept rows were gathered)
KEYE_TEMP_LIMIT_GB = {"decode_chunk": 0.6, "prefill": 0.4}


def _keye_model():
    from znicz_tpu.workflow.sparse_gqa_lm import SparseGQAMoEModel

    return SparseGQAMoEModel(
        n_layers=6, n_heads=32, n_kv_heads=4, head_dim=128, index_n_heads=16,
        index_head_dim=64, index_topk=KEYE["top_k"], top_k=8,
        max_positions=KEYE["rung"] * KEYE["block"],
    )


def _keye_params(spec):
    a, bf, f32 = KEYE, jnp.bfloat16, jnp.float32
    d, f, e = a["d"], a["f"], a["experts"]
    block = {
        "attn_norm": ((d,), f32), "wq": ((d, 4096), bf), "wk": ((d, 512), bf),
        "wv": ((d, 512), bf), "q_norm": ((128,), f32), "k_norm": ((128,), f32),
        "wo": ((4096, d), bf), "wq_idx": ((d, 1024), bf), "wk_idx": ((d, 64), bf),
        "k_idx_gain": ((64,), f32), "k_idx_bias": ((64,), f32),
        "w_idx": ((d, 16), bf), "ffn_norm": ((d,), f32), "router": ((d, e), bf),
        "experts_gate": ((e, d, f), bf), "experts_up": ((e, d, f), bf),
        "experts_down": ((e, f, d), bf),
    }
    return (
        [{"embed": spec((a["vocab"], d), bf)}]
        + [{k: spec(*v) for k, v in block.items()} for _ in range(6)]
        + [{"final_norm": spec((d,), f32), "head": spec((d, a["vocab"]), bf)}]
    )


@pytest.mark.parametrize("program", list(KEYE_TEMP_LIMIT_GB))
def test_the_kept_rows_are_fetched_from_pools_stored_as_they_are_computed_on(
    chip, program, monkeypatch
):
    from znicz_tpu.core import backend

    monkeypatch.setattr(backend, "on_tpu", lambda: True)
    monkeypatch.setattr(backend, "pallas_interpret", lambda: False)
    a, model = KEYE, _keye_model()

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    i32, f32 = jnp.int32, jnp.float32
    params = _keye_params(spec)
    pools = jax.tree.map(
        lambda p: spec(p.shape, p.dtype),
        jax.eval_shape(
            lambda: model.init_pools(params, a["n_blocks"], a["block"])
        ),
    )
    assert [p["kv"].shape for p in pools] == [(2048, 128, 1024)] * 6
    assert [p["idx"].shape for p in pools] == [(2048, 128, 128)] * 6
    t_max = a["rung"] * a["block"]
    scalar_f32, key = spec((), f32), spec((2,), jnp.uint32)
    tower = dict(
        n_heads=32, block_size=a["block"], moe_top_k=1, moe_dispatch="dense",
        model=model,
    )
    with jax.default_matmul_precision("default"):
        if program == "decode_chunk":
            lowered = engine._paged_decode_chunk.lower(
                params, pools, {"global": spec((a["slots"], a["rung"]), i32)},
                spec((5, a["slots"]), i32), scalar_f32, scalar_f32, key,
                chunk=4, t_max=t_max, eos_id=0, **SAMPLING, **tower,
            )
        else:
            lowered = engine._paged_prefill_prog.lower(
                params, pools, {"global": spec((a["rung"],), i32)},
                spec((1, t_max), i32), spec((3,), i32), scalar_f32,
                scalar_f32, key, **SAMPLING, **tower,
            )
        compiled = lowered.compile()  # raises where the chip would refuse it
    text = compiled.as_text()
    mem = compiled.memory_analysis()
    print(
        f"keye {program}: arguments {mem.argument_size_in_bytes / GB:.2f} GB, "
        f"temporaries {mem.temp_size_in_bytes / GB:.2f} GB, "
        f"{text.count('tpu_custom_call')} kernels"
    )
    # neither pool is copied or re-tiled, and no [v, k] window of the
    # table's width is gathered: a decode step fetches the kept rows, a
    # prefill chunk walks the table a few entries at a time
    kv_row, idx_row = a["block"] * 1024, a["block"] * 128
    window = (1 if program == "prefill" else a["slots"]) * a["rung"] * kv_row
    moved = _relayouts(text, 2048 * kv_row, 2048 * idx_row, window)
    assert not moved, f"{program} moves a pool or a window: {sorted(set(moved))}"
    assert not [
        dims for dims in _SHAPE.findall(text)
        if int(np.prod([int(d) for d in dims.split(",")])) == window
    ], f"{program} gathers the table's whole width"
    fetched = a["slots"] * a["top_k"] * 1024
    assert program != "decode_chunk" or not [
        dims for dims in _SHAPE.findall(text)
        if int(np.prod([int(d) for d in dims.split(",")])) == fetched
    ], "the decode chunk holds every slot's kept rows as an array"
    assert mem.temp_size_in_bytes < KEYE_TEMP_LIMIT_GB[program] * GB, (
        f"{program} holds {mem.temp_size_in_bytes / GB:.2f} GB of temporaries"
    )
    # weights 8.75 GB + pools 3.62 GB + temporaries fit 15.75 GiB
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75 * 2**30
    # the three grouped products of every layer's experts are the kernel,
    # and in a decode step every layer's indexer and its attention over the
    # kept rows are one each too
    assert text.count("tpu_custom_call") >= (
        3 * 6 + (6 + 6 if program == "decode_chunk" else 0)
    )
    if program == "decode_chunk":
        assert "kept_gqa_decode" in text


# -- [v, k] rows of 2,048 lanes, query heads a layer kind, at laguna-xs2-stage1's
#
# 7 layers (full and dense, sliding x 3, full, sliding x 2) at hidden 2,048;
# 48 query heads on the full layers and 64 on the sliding ones over 8 K/V
# heads of 128 (groups of 6 and of 8), a gate a head, 256 experts of width
# 512 all held beside a shared one, the whole vocabulary of 100,352,
# bfloat16; 48 slots, block 128, 1,280 global blocks and 384 window blocks of
# 2,048 lanes, the widest global rung (176 blocks = 22,528 positions) beside
# the window kind's ring of 6 (512 keys, chunks of 4 steps).  A decode step
# of either kind reads the pool in place through the kernel the other towers
# use (one Pallas call a layer); a prefill chunk walks the table with the
# products grouped a K/V head (no [8,192 x 22,528] score matrix, no
# block-diagonal zeros).

LG = dict(d=2048, heads={"global": 48, "window": 64}, kv_heads=8, dim=128,
          f=512, f_dense=8192, experts=256, vocab=100352, slots=48, block=128,
          rung=176, ring=6, chunk=4, max_seq=22528,
          windowed=(False, True, True, True, False, True, True),
          n_blocks={"global": 1280, "window": 384})


def _laguna_model():
    from znicz_tpu.workflow.gated_window_lm import GatedWindowGQAMoEModel

    return GatedWindowGQAMoEModel(
        n_heads=64, global_heads=48, n_kv_heads=8, head_dim=128, top_k=8,
        window=512, windowed=LG["windowed"], max_positions=LG["max_seq"],
        routed_scaling_factor=2.5, global_rope_theta=5e5, global_rotary_dim=64,
        rope_factor=64.0, rope_original_max=4096, rope_beta_fast=64.0,
        rope_beta_slow=1.0, attention_factor=1.4158883083359672,
    )


def _laguna_params(spec):
    a, bf, f32 = LG, jnp.bfloat16, jnp.float32
    d, kv, e, f = a["d"], a["kv_heads"] * a["dim"], a["experts"], a["f"]
    blocks = []
    for layer, windowed in enumerate(a["windowed"]):
        h = a["heads"]["window" if windowed else "global"]
        leaves = {
            "attn_norm": ((d,), f32), "wq": ((d, h * a["dim"]), bf),
            "wk": ((d, kv), bf), "wv": ((d, kv), bf), "wg": ((d, h), bf),
            "wo": ((h * a["dim"], d), bf), "ffn_norm": ((d,), f32),
        }
        if layer == 0:
            leaves.update(
                w_gate=((d, a["f_dense"]), bf), w_up=((d, a["f_dense"]), bf),
                w_down=((a["f_dense"], d), bf),
            )
        else:
            leaves.update(
                router=((d, e), bf), router_bias=((e,), f32),
                experts_gate=((e, d, f), bf), experts_up=((e, d, f), bf),
                experts_down=((e, f, d), bf), shared_gate=((d, f), bf),
                shared_up=((d, f), bf), shared_down=((f, d), bf),
            )
        blocks.append({k: spec(*v) for k, v in leaves.items()})
    return (
        [{"embed": spec((a["vocab"], d), bf)}] + blocks
        + [{"final_norm": spec((d,), f32), "head": spec((d, a["vocab"]), bf)}]
    )


LAGUNA_TEMP_LIMIT_GB = {"decode_chunk": 0.8, "prefill": 0.8}


@pytest.mark.parametrize("program", list(LAGUNA_TEMP_LIMIT_GB))
def test_rows_of_2048_lanes_under_heads_by_kind_are_stored_as_they_are_computed_on(
    chip, program, monkeypatch
):
    from znicz_tpu.core import backend

    monkeypatch.setattr(backend, "on_tpu", lambda: True)
    monkeypatch.setattr(backend, "pallas_interpret", lambda: False)
    a, model = LG, _laguna_model()

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    i32, f32 = jnp.int32, jnp.float32
    params = _laguna_params(spec)
    pools = jax.tree.map(
        lambda p: spec(p.shape, p.dtype),
        jax.eval_shape(lambda: model.init_pools(params, a["n_blocks"], a["block"])),
    )
    assert [p["kv"].shape[0] for p in pools] == [1280, 384, 384, 384, 1280, 384, 384]
    assert pools[0]["kv"].shape[1:] == (a["block"], 2048)
    state = spec((5, a["slots"]), i32)
    scalar_f32, key = spec((), f32), spec((2,), jnp.uint32)
    tower = dict(
        n_heads=48, block_size=a["block"], moe_top_k=1, moe_dispatch="dense",
        model=model,
    )
    with jax.default_matmul_precision("default"):
        if program == "decode_chunk":
            tables = {
                "global": spec((a["slots"], a["rung"]), i32),
                "window": spec((a["slots"], a["ring"]), i32),
            }
            lowered = engine._paged_decode_chunk.lower(
                params, pools, tables, state, scalar_f32, scalar_f32, key,
                chunk=a["chunk"], t_max=a["max_seq"], eos_id=0, **SAMPLING, **tower,
            )
        else:
            table = {
                "global": spec((a["rung"],), i32), "window": spec((a["ring"],), i32),
            }
            lowered = engine._paged_prefill_prog.lower(
                params, pools, table, spec((1, a["max_seq"]), i32),
                spec((3,), i32), scalar_f32, scalar_f32, key, **SAMPLING, **tower,
            )
        compiled = lowered.compile()  # raises where the chip would refuse it
    text = compiled.as_text()
    row = a["block"] * 2048
    # no pool is copied or re-tiled, and no table's width of rows is gathered
    moved = _relayouts(text, 1280 * row, 384 * row)
    assert not moved, f"{program} moves a pool: {sorted(set(moved))}"
    gathered = (
        a["slots"] * a["rung"] * row, a["slots"] * a["ring"] * row, a["rung"] * row,
    )
    assert not [
        dims for dims in _SHAPE.findall(text)
        if int(np.prod([int(d) for d in dims.split(",")])) in gathered
    ], f"{program} gathers a table's width of rows"
    mem = compiled.memory_analysis()
    print(
        f"laguna {program}: arguments {mem.argument_size_in_bytes / GB:.2f} GB, "
        f"temporaries {mem.temp_size_in_bytes / GB:.2f} GB"
    )
    assert mem.temp_size_in_bytes < LAGUNA_TEMP_LIMIT_GB[program] * GB, (
        f"{program} holds {mem.temp_size_in_bytes / GB:.2f} GB of temporaries"
    )
    # weights 11.13 GB + pools 2.35 GB + temporaries fit 15.75 GiB with the
    # 0.8 GB to spare ISSUE 44 asks for
    assert (
        mem.argument_size_in_bytes + mem.temp_size_in_bytes
        < 15.75 * 2**30 - 0.8 * GB
    )
    # the three grouped products of the six routed layers are the kernel,
    # and in a decode step every layer's attention is one too
    # (a global layer's two: the pass over the blocks a tile of rows share
    # and the pass over each row's own)
    kernels = text.count("tpu_custom_call")
    assert kernels >= 3 * 6 + (7 + 2 if program == "decode_chunk" else 0)
