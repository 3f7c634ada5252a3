"""Fused RBM CD-k kernel: the whole Gibbs chain in one VMEM pass.

TPU-native equivalent of the reference's ``rbm.cl/.cu`` sampling kernels
[SURVEY.md 2.2 row "RBM", §7 "Kohonen/RBM ... custom update functions +
Pallas kernels"; BASELINE configs[2] exercises the MNIST RBM].  The jnp
twin (:func:`znicz_tpu.ops.rbm.cd_step`) pays for each Gibbs step with two
HBM-roundtripped matmuls plus *threefry* bernoulli sampling — on TPU the
counter-based RNG alone costs more VPU work than the matmuls for RBM-sized
layers.  This kernel runs the full chain out of VMEM and samples with the
TPU's hardware PRNG (``pltpu.prng_random_bits``), so sampling is one
compare per element (tests/test_pallas.py holds the TPU timing
assertion against the twin).

Like the Kohonen kernel, the pallas_call emits the RAW CD statistics
(positive-minus-negative weight accumulator, bias deltas, masked error and
count) and the cheap scaled update runs outside where XLA fuses it — which
is exactly what makes it data-parallel: under a sharded batch each device
accumulates its local statistics and one psum over the data axis recovers
the full-batch update (``cd_step(..., mesh=...)``).

RNG note: hardware bits, not threefry — the sampled chain differs from the
jnp twin's at equal seeds (both are valid CD samplers).  Golden tests pin
the deterministic regime (saturated probabilities) where both must agree
exactly; statistical tests cover the rest.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh

from znicz_tpu.core import backend

# single-block kernel: everything resident in VMEM.  RBM-sized problems
# (MNIST: 784x1024 weights, batches <= 1024) fit with room to spare.
# Above this budget cd_step raises up front (no silent Mosaic failure);
# RBMWorkflow's impl="auto" checks fits_vmem and picks the jnp twin.
VMEM_BUDGET_BYTES = 10 * 1024 * 1024


def _uniform(shape):
    """U[0,1) from the hardware PRNG: 24 low bits -> float32.

    prng_random_bits is typed int32 — a plain ``>> 8`` would be an
    ARITHMETIC shift leaving half the draws negative (every bernoulli
    then fires with prob 0.5 + p/2); masking to 24 bits is sign-safe."""
    bits = pltpu.prng_random_bits(shape)
    return (bits & jnp.int32(0x00FFFFFF)).astype(jnp.float32) * (
        1.0 / (1 << 24)
    )


def _cd_kernel(
    v0_ref,  # [B, V]
    mask_ref,  # [B, 1]
    w_ref,  # [V, H]
    vb_ref,  # [1, V]
    hb_ref,  # [1, H]
    seed_ref,  # [1, 1] SMEM int32
    uh_ref,  # [1+cd_k, B, H] precomputed uniforms (interpret mode only)
    uv_ref,  # [cd_k, B, V] precomputed uniforms (interpret mode only)
    dw_ref,  # out [V, H]  (v0'h0p - vk'hkp, mask-weighted)
    dvb_ref,  # out [1, V]
    dhb_ref,  # out [1, H]
    stats_ref,  # out [1, 2]: (masked err sum, mask sum)
    *,
    cd_k: int,
    hw_rng: bool,
):
    # hw_rng is static: on TPU the hardware PRNG generates the bernoulli
    # draws in-kernel; interpret mode (no Mosaic RNG lowering) reads
    # host-precomputed uniforms instead — same kernel, dead branch removed
    if hw_rng:
        pltpu.prng_seed(seed_ref[0, 0])

        def uh(i, shape):
            return _uniform(shape)

        uv = uh
    else:

        def uh(i, shape):
            return uh_ref[i]

        def uv(i, shape):
            return uv_ref[i]

    v0 = v0_ref[:]
    mask = mask_ref[:]  # [B, 1]
    w = w_ref[:]
    vb = vb_ref[:]
    hb = hb_ref[:]
    h0p = jax.nn.sigmoid(
        jnp.dot(v0, w, preferred_element_type=jnp.float32) + hb
    )
    h = (uh(0, h0p.shape) < h0p).astype(jnp.float32)
    for k in range(cd_k):  # static unroll: the whole chain stays in VMEM
        vp = jax.nn.sigmoid(
            jnp.dot(h, w.T, preferred_element_type=jnp.float32) + vb
        )
        v = (uv(k, vp.shape) < vp).astype(jnp.float32)
        hp = jax.nn.sigmoid(
            jnp.dot(v, w, preferred_element_type=jnp.float32) + hb
        )
        h = (uh(k + 1, hp.shape) < hp).astype(jnp.float32)
    v0m = v0 * mask
    vpm = vp * mask
    dw_ref[:] = jnp.dot(
        v0m.T, h0p, preferred_element_type=jnp.float32
    ) - jnp.dot(vpm.T, hp, preferred_element_type=jnp.float32)
    dvb_ref[:] = jnp.sum((v0 - vp) * mask, axis=0, keepdims=True)
    dhb_ref[:] = jnp.sum((h0p - hp) * mask, axis=0, keepdims=True)
    err = jnp.sum(
        jnp.mean(jnp.square(v0 - vp), axis=1, keepdims=True) * mask
    )
    # Mosaic rejects scalar stores to VMEM: write the row as one 2-D store
    stats_ref[:] = jnp.concatenate(
        [err.reshape(1, 1), jnp.sum(mask).reshape(1, 1)], axis=1
    )


def fits_vmem(batch: int, n_visible: int, n_hidden: int) -> bool:
    floats = (
        3 * batch * n_visible  # v0, vp, v
        + 3 * batch * n_hidden  # h0p, hp, h
        + 2 * n_visible * n_hidden  # w, dw
    )
    return floats * 4 <= VMEM_BUDGET_BYTES


def _statistics(params, v0, mask, seed, *, cd_k):
    b, v = v0.shape
    h = params["hbias"].shape[0]
    interpret = backend.pallas_interpret()
    if interpret:
        # no Mosaic RNG off-TPU: precompute the chain's uniforms from the
        # seed (deterministic given seed, like the hardware path)
        key = jax.random.fold_in(
            # deliberately seed-deterministic, mirroring the hardware
            # RNG path (same seed -> same chain on every backend); NOT a
            # training stream, so the prng registry is the wrong source
            jax.random.key(0),  # znicz-check: disable=ZNC004
            jnp.asarray(seed, jnp.int32),
        )
        kh, kv = jax.random.split(key)
        uh = jax.random.uniform(kh, (1 + cd_k, b, h), jnp.float32)
        uv = jax.random.uniform(kv, (cd_k, b, v), jnp.float32)
    else:  # dummies; the hw_rng branch never reads them
        uh = jnp.zeros((1, 1, 1), jnp.float32)
        uv = jnp.zeros((1, 1, 1), jnp.float32)
    return pl.pallas_call(
        partial(_cd_kernel, cd_k=cd_k, hw_rng=not interpret),
        out_shape=(
            jax.ShapeDtypeStruct((v, h), jnp.float32),
            jax.ShapeDtypeStruct((1, v), jnp.float32),
            jax.ShapeDtypeStruct((1, h), jnp.float32),
            jax.ShapeDtypeStruct((1, 2), jnp.float32),
        ),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_specs=(
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ),
        interpret=interpret,
    )(
        v0,
        mask[:, None],
        params["weights"],
        params["vbias"][None, :],
        params["hbias"][None, :],
        jnp.asarray(seed, jnp.int32).reshape(1, 1),
        uh,
        uv,
    )


def _apply_update(params, dw, dvb, dhb, stats, learning_rate):
    n_valid = jnp.maximum(stats[0, 1], 1.0)
    lr = jnp.asarray(learning_rate, jnp.float32) / n_valid
    new = {
        "weights": params["weights"] + lr * dw,
        "vbias": params["vbias"] + lr * dvb[0],
        "hbias": params["hbias"] + lr * dhb[0],
    }
    return new, stats[0, 0] / n_valid


def cd_step(
    params,
    v0,
    seed,
    *,
    learning_rate,
    cd_k: int = 1,
    mask=None,
    mesh: Mesh | None = None,
    data_axis: str = "data",
):
    """Fused twin of ops.rbm.cd_step; ``seed`` is an int32 scalar (e.g. the
    train-state step) instead of a jax key — the hardware PRNG is seeded
    inside the kernel.  ``mesh``: treat v0/mask as sharded over
    ``mesh[data_axis]``; local statistics psum into the exact full-batch
    update (each shard gets a decorrelated seed)."""
    b, v = v0.shape
    h = params["hbias"].shape[0]
    if mesh is not None:
        b = -(-b // mesh.shape[data_axis])  # per-shard batch
    if not fits_vmem(b, v, h):
        raise ValueError(
            f"RBM problem (batch={b}, visible={v}, hidden={h}) exceeds the "
            f"single-block VMEM budget ({VMEM_BUDGET_BYTES >> 20} MiB); "
            "use ops.rbm.cd_step (the jnp twin) or RBMWorkflow's "
            "impl='auto'"
        )
    if mask is None:
        mask = jnp.ones((v0.shape[0],), v0.dtype)
    if mesh is None:
        dw, dvb, dhb, stats = _statistics(
            params, v0, mask, seed, cd_k=cd_k
        )
        return _apply_update(params, dw, dvb, dhb, stats, learning_rate)

    from jax.sharding import PartitionSpec as P

    def local(params, v0, mask, seed, lr):
        # stride by the shard count so streams never collide across steps:
        # seed+axis_index would make (step s, shard d) replay (step s+1,
        # shard d-1) bit-for-bit when the caller passes seed=step
        n_shards = jax.lax.psum(1, data_axis)
        shard_seed = seed * n_shards + jax.lax.axis_index(data_axis)
        dw, dvb, dhb, stats = _statistics(
            params, v0, mask, shard_seed, cd_k=cd_k
        )
        dw, dvb, dhb, stats = jax.lax.psum(
            (dw, dvb, dhb, stats), data_axis
        )
        return _apply_update(params, dw, dvb, dhb, stats, lr)

    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(), P(data_axis), P(data_axis), P(), P()),
        out_specs=P(),
        check_vma=False,  # pallas out_shape carries no vma; psum replicates
    )
    return fn(
        params,
        v0,
        mask,
        jnp.asarray(seed, jnp.int32),
        jnp.asarray(learning_rate, jnp.float32),
    )
