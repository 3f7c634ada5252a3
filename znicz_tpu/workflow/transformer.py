"""Causal transformer language-model workflow.

NOT in the reference (VELES predates transformers, SURVEY.md 5.7) — this is
the workflow that makes the long-context stack user-facing: the attention op
(:mod:`znicz_tpu.ops.attention`), optional ring-attention sequence
parallelism (:mod:`znicz_tpu.parallel.ring_attention`), layer norm, and the
standard loader/decision/snapshotter machinery, trained with next-token
cross-entropy under the same momentum-SGD update rule as every other
workflow.

Params are a list of flat per-layer dicts so the optimizer's per-layer
HyperParams and ``*_bias`` multiplier rules apply unchanged.
"""

from __future__ import annotations

import re
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from znicz_tpu.core import backend, prng
from znicz_tpu.loader.base import Loader
from znicz_tpu.nn import optimizer
from znicz_tpu.nn.decision import Decision
from znicz_tpu.nn.train_state import TrainState
from znicz_tpu.ops import attention
from znicz_tpu.ops.filling import fill
from znicz_tpu.parallel.mesh import MODEL_AXIS
from znicz_tpu.ops.normalization import layer_norm
from znicz_tpu.workflow.snapshotter import Snapshotter
from znicz_tpu.workflow.workflow import Workflow, _global_norm


def init_lm_params(
    vocab: int,
    d_model: int,
    n_layers: int,
    n_heads: int,
    max_seq: int,
    *,
    d_ff: Optional[int] = None,
    moe_experts: int = 0,
    rand_name: str = "default",
):
    """[embed, block_0, ..., block_{L-1}, head] — flat dicts per layer.

    ``moe_experts > 1``: each block's FFN becomes a gated
    mixture-of-experts (:mod:`znicz_tpu.ops.moe`) with ``moe_experts``
    experts of hidden size ``d_ff`` — the EP axis composes into the LM.
    """
    gen = prng.get(rand_name)
    d_ff = d_ff or 4 * d_model
    std = 1.0 / np.sqrt(d_model)
    params = [
        {
            "embed": jnp.asarray(fill(gen, (vocab, d_model), "gaussian", std)),
            "pos": jnp.asarray(fill(gen, (max_seq, d_model), "gaussian", std)),
        }
    ]
    for _ in range(n_layers):
        block = {
            "ln1_scale": jnp.ones((d_model,)),
            "ln1_bias": jnp.zeros((d_model,)),
            "ln2_scale": jnp.ones((d_model,)),
            "ln2_bias": jnp.zeros((d_model,)),
        }
        if moe_experts > 1:
            from znicz_tpu.ops import moe as moe_op

            m = moe_op.init_params(
                d_model, d_ff, moe_experts, rand_name=rand_name
            )
            # names end in "bias" so HyperParams' *_bias multiplier rules
            # classify them like every other workflow's biases
            block.update({k: m[v] for k, v in MOE_KEY_MAP.items()})
        else:
            block.update(
                w_up=jnp.asarray(
                    fill(gen, (d_model, d_ff), "gaussian", std)
                ),
                up_bias=jnp.zeros((d_ff,)),
                w_down=jnp.asarray(
                    fill(
                        gen, (d_ff, d_model), "gaussian",
                        1.0 / np.sqrt(d_ff),
                    )
                ),
                down_bias=jnp.zeros((d_model,)),
            )
        block.update(
            attention.init_mha_params(
                d_model, n_heads, rand_name=rand_name
            )
        )
        params.append(block)
    params.append(
        {"head": jnp.asarray(fill(gen, (d_model, vocab), "gaussian", std))}
    )
    return params


def _embed_tokens(embed, tokens):
    t = tokens.shape[1]
    return embed["embed"][tokens] + embed["pos"][:t][None, :, :]


# MoE param names in the block's FLAT dict -> ops/moe's schema.  THE one
# mapping: init_lm_params, _block_ffn, lm_tp_rules and export's guard all
# derive from it, so adding/renaming an MoE leaf cannot silently miss a
# site (a leaf absent from the TP list would fall through to replicated
# placement while its siblings shard on the expert dim).
MOE_KEY_MAP = {
    "moe_router": "router",
    "moe_w_up": "w1",      # [E, D, F]
    "moe_up_bias": "b1",   # [E, F]
    "moe_w_down": "w2",    # [E, F, D]
    "moe_down_bias": "b2",  # [E, D]
}
# every non-router leaf carries a leading expert dim (EP shards it)
_MOE_EXPERT_SHARDED = tuple(k for k in MOE_KEY_MAP if k != "moe_router")


def _block_ffn(block, h, *, moe_top_k=1, moe_dispatch="dense"):
    """The block's position-wise FFN: dense two-layer tanh, or — when the
    block carries MoE params — a gated mixture of experts over the
    flattened token dim."""
    if "moe_router" in block:
        from znicz_tpu.ops import moe as moe_op

        b, t, d = h.shape
        y = moe_op.apply(
            {v: block[k] for k, v in MOE_KEY_MAP.items()},
            h.reshape(b * t, d),
            top_k=moe_top_k,
            dispatch=moe_dispatch,
        )
        return y.reshape(b, t, d)
    h = jnp.tanh(h @ block["w_up"] + block["up_bias"])
    return h @ block["w_down"] + block["down_bias"]


def _block_forward(block, x, *, n_heads, attention_fn=None,
                   moe_top_k=1, moe_dispatch="dense"):
    """One pre-LN transformer block (the ONLY definition — lm_apply and the
    pipelined stage_fn both call it, so they cannot drift apart)."""
    attention_fn = attention_fn or attention.dot_product_attention
    h = layer_norm(x, block["ln1_scale"], block["ln1_bias"])
    x = x + attention.mha(
        block, h, n_heads=n_heads, causal=True, attention_fn=attention_fn
    )
    h = layer_norm(x, block["ln2_scale"], block["ln2_bias"])
    return x + _block_ffn(
        block, h, moe_top_k=moe_top_k, moe_dispatch=moe_dispatch
    )


def _block_forward_tp(block, x, *, n_heads_local, tp_axis, attention_fn=None,
                      moe_top_k=1):
    """:func:`_block_forward` for MANUAL (shard_map) tensor parallelism:
    the block's weights are model-axis-LOCAL shards (Megatron column
    placement for wq/wk/wv/w_up — so this device owns ``n_heads_local``
    heads and a 1/mp slice of the FFN — row placement for wo/w_down), and
    the two residual contributions are partial products ``psum``-ed over
    ``tp_axis``.  An MoE block shards its EXPERTS over ``tp_axis`` instead
    (router replicated; :func:`znicz_tpu.ops.moe.apply_local_shard`
    computes this shard's gate-weighted expert contribution, and the same
    psum combines).  Activations enter and leave replicated over the model
    axis; same math as :func:`_block_forward` up to summation order.
    Used inside the pipeline's shard_map, where GSPMD cannot insert the
    collectives for us (SURVEY.md 2.5 beyond-parity: PPxTPxDP)."""
    attention_fn = attention_fn or attention.dot_product_attention
    h = layer_norm(x, block["ln1_scale"], block["ln1_bias"])
    # mha over the LOCAL head subset computes exactly the partial product
    # o @ wo_local this device owes the psum (one mha definition — same
    # no-drift rationale as _block_forward)
    att = attention.mha(
        block, h, n_heads=n_heads_local, causal=True,
        attention_fn=attention_fn,
    )
    x = x + jax.lax.psum(att, tp_axis)
    h = layer_norm(x, block["ln2_scale"], block["ln2_bias"])
    if "moe_router" in block:
        from znicz_tpu.ops import moe as moe_op

        b, t, d = h.shape
        partial_y = moe_op.apply_local_shard(
            {v: block[k] for k, v in MOE_KEY_MAP.items()},
            h.reshape(b * t, d),
            top_k=moe_top_k,
            shard_index=jax.lax.axis_index(tp_axis),
        )
        return x + jax.lax.psum(partial_y.reshape(b, t, d), tp_axis)
    h = jnp.tanh(h @ block["w_up"] + block["up_bias"])
    return x + jax.lax.psum(h @ block["w_down"], tp_axis) + block["down_bias"]


def lm_apply(params, tokens, *, n_heads, attention_fn=None, remat=False,
             moe_top_k=1, moe_dispatch="dense"):
    """tokens [B, T] int32 -> logits [B, T, vocab].

    ``remat``: wrap each block in ``jax.checkpoint`` — activations are
    recomputed in the backward instead of stored, cutting training
    activation memory from O(L·T·D) to O(T·D) per microstep at ~1/3 extra
    FLOPs.  The long-context lever jax gives for free; numerics are
    unchanged (same ops, re-run)."""
    attention_fn = attention_fn or attention.dot_product_attention
    blk = partial(
        _block_forward, n_heads=n_heads, attention_fn=attention_fn,
        moe_top_k=moe_top_k, moe_dispatch=moe_dispatch,
    )
    if remat:
        blk = jax.checkpoint(blk)
    x = _embed_tokens(params[0], tokens)
    for block in params[1:-1]:
        x = blk(block, x)
    return x @ params[-1]["head"]


def stack_lm_blocks(params, n_stages: int):
    """[embed, block_0..L-1, head] -> {"embed", "stages", "head"} with the
    blocks grouped into ``n_stages`` equal stage-groups and stacked on a
    leading stage dim (the :mod:`znicz_tpu.parallel.pipeline` layout).
    Initialization draw order is untouched — the restructure happens after
    ``init_lm_params``."""
    from znicz_tpu.parallel.pipeline import stack_stage_params

    blocks = params[1:-1]
    if len(blocks) % n_stages:
        raise ValueError(
            f"n_layers={len(blocks)} not divisible by pipeline stages "
            f"{n_stages}"
        )
    g = len(blocks) // n_stages
    groups = [blocks[s * g:(s + 1) * g] for s in range(n_stages)]
    return {
        "embed": params[0],
        "stages": stack_stage_params(groups),
        "head": params[-1],
    }


def lm_apply_pipelined(
    params_pp, tokens, *, n_heads, mesh, n_microbatches,
    data_axis=None, tp_axis=None, attention_fn=None, remat=False,
    moe_top_k=1, moe_dispatch="dense",
):
    """tokens [B, T] -> logits, with the block tower pipelined over the
    mesh's ``pipe`` axis (embed/head run outside the shard_map);
    ``data_axis`` shards microbatch rows for DPxPP composition;
    ``tp_axis`` additionally shards each stage's weights over the model
    axis (Megatron column/row inside the pipeline shard_map — the 3-axis
    DPxPPxTP composition)."""
    from znicz_tpu.parallel.pipeline import pipelined_model_apply

    def embed_fn(p, tok):
        return _embed_tokens(p, tok)

    param_spec_fn = None
    if tp_axis is not None:
        n_model = mesh.shape[tp_axis]
        if n_heads % n_model:
            raise ValueError(
                f"n_heads={n_heads} not divisible by model axis {n_model}"
            )
        blk = partial(
            _block_forward_tp,
            n_heads_local=n_heads // n_model,
            tp_axis=tp_axis,
            attention_fn=attention_fn,
            moe_top_k=moe_top_k,
        )
        param_spec_fn = _pp_stage_tp_specs(tp_axis)
    else:
        blk = partial(
            _block_forward, n_heads=n_heads, attention_fn=attention_fn,
            moe_top_k=moe_top_k, moe_dispatch=moe_dispatch,
        )
    if remat:  # recompute per-block activations in the backward pipeline
        blk = jax.checkpoint(blk)

    def stage_fn(blocks, x):
        for block in blocks:  # this stage's group of transformer blocks
            x = blk(block, x)
        return x

    def head_fn(p, x):
        return x @ p["head"]

    return pipelined_model_apply(
        params_pp, tokens,
        embed_fn=embed_fn, stage_fn=stage_fn, head_fn=head_fn,
        mesh=mesh, n_microbatches=n_microbatches, data_axis=data_axis,
        param_spec_fn=param_spec_fn,
        # flash attention inside the stage is a pallas_call: no vma
        # annotation on its out_shapes, so the check must be off for it
        check_vma=attention_fn is None,
    )


def lm_pp_rules(path: str, leaf):
    """DataParallel param_rules for the pipelined LM: stacked stage params
    shard over ``pipe`` (chunk-per-device), embed/head replicate."""
    from jax.sharding import PartitionSpec as P

    from znicz_tpu.parallel.mesh import PIPE_AXIS

    if "'stages'" in path:
        return P(PIPE_AXIS, *([None] * (leaf.ndim - 1)))
    return P()


def _stage_tp_spec(key: str, ndim: int, tp_axis: str = MODEL_AXIS):
    """PartitionSpec for ONE stacked stage leaf [S, ...] under PPxTP:
    stage dim over ``pipe``, weight dims per the Megatron role
    (column: wq/wk/wv/w_up + up_bias; row: wo/w_down; MoE expert leaves
    shard their leading expert dim — manual EP; the router replicates);
    the rest replicated over ``tp_axis``."""
    from jax.sharding import PartitionSpec as P

    from znicz_tpu.parallel.mesh import PIPE_AXIS

    if key in _MOE_EXPERT_SHARDED:
        return P(PIPE_AXIS, tp_axis, *([None] * (ndim - 2)))
    if key in ("wq", "wk", "wv", "w_up"):
        return P(PIPE_AXIS, None, tp_axis)
    if key in ("wo", "w_down"):
        return P(PIPE_AXIS, tp_axis, None)
    if key == "up_bias":
        return P(PIPE_AXIS, tp_axis)
    return P(PIPE_AXIS, *([None] * (ndim - 1)))


_KEY_PAT = re.compile(r"\['(\w+)'\]")


def _last_key(path: str) -> str:
    """Last ['name'] component of a jax keystr path."""
    keys = _KEY_PAT.findall(path)
    return keys[-1] if keys else ""


def _pp_stage_tp_specs(tp_axis):
    """pipeline_apply ``param_spec_fn`` for the LM stage tower under TP
    (weight placement and the psums in :func:`_block_forward_tp` use the
    SAME axis)."""

    def spec_fn(path: str, leaf):
        return _stage_tp_spec(_last_key(path), leaf.ndim, tp_axis)

    return spec_fn


def lm_pp_tp_rules(path: str, leaf):
    """DataParallel param_rules for the PPxTP LM: stacked stage weights
    shard over (pipe, model) per their Megatron role; embed/head
    replicate (they run outside the pipeline shard_map)."""
    from jax.sharding import PartitionSpec as P

    if "'stages'" in path:
        return _stage_tp_spec(_last_key(path), leaf.ndim)
    return P()


def lm_tp_rules(path: str, leaf):
    """Head/row-column-aware tensor-parallel placement for the LM params
    (plugs into ``DataParallel(param_rules=...)``).

    Column-parallel (shard the output-features dim over ``model``): the QKV
    projections — the inner dim is heads*head_dim, so this IS head sharding
    when n_heads divides the axis — plus ``w_up`` and the vocab dim of the
    ``head`` (the loss's log-softmax reduces over it with a psum GSPMD
    inserts).  Row-parallel (shard the input dim; XLA psums the partial
    products): ``wo`` and ``w_down``.  Everything else (embeddings, layer
    norms, biases except up_bias) is replicated.
    """
    from jax.sharding import PartitionSpec as P

    if any(f"'{k}'" in path for k in _MOE_EXPERT_SHARDED):
        # expert parallelism: the leading expert dim shards over model
        # (ops/moe.expert_sharding's placement; GSPMD psums the combine)
        return P(MODEL_AXIS, *([None] * (leaf.ndim - 1)))
    if any(k in path for k in ("'wq'", "'wk'", "'wv'", "'w_up'", "'head'")):
        return P(None, MODEL_AXIS)
    if any(k in path for k in ("'wo'", "'w_down'")):
        return P(MODEL_AXIS, None)
    if "'up_bias'" in path:
        return P(MODEL_AXIS)
    return P()


class TransformerLMWorkflow(Workflow):
    """Next-token LM training over integer-sequence loaders.

    Loader contract: ``data[split]`` is [N, T] integer tokens (stored as any
    numeric dtype); the per-sample ``mask`` marks valid rows as usual.

    ``sequence_parallel``: shard the sequence axis over a mesh's data axis
    with ring attention (set ``parallel`` too for the batch placement).
    ``tensor_parallel``: shard attention heads + FFN + vocab head over the
    mesh's ``model`` axis (``lm_tp_rules``); composes with DP and SP on the
    same mesh.  Requires ``parallel=DataParallel(mesh)`` with a model axis
    > 1 and n_heads divisible by it.
    ``pipeline_parallel``: pipeline the block tower over the mesh's
    ``pipe`` axis (GPipe microbatching, ``parallel/pipeline.py``); pass a
    ``mesh`` with a pipe axis whose size divides ``n_layers``, or compose
    with data parallelism by passing ``parallel=DataParallel(mesh)`` over
    a (data, pipe) mesh — each data replica runs its own pipeline on its
    batch shard and stage grads all-reduce over ``data``.  Stage params
    live chunk-per-device; embed/head run outside the pipeline.
    ``pipeline_microbatches`` defaults to ``6 * n_stages`` (GPipe bubble
    < 0.15 for every stage count), clamped to the largest count compatible
    with the batch size and data axis — a warning fires when the clamp
    leaves a larger bubble.  Composes with ``tensor_parallel`` on a
    (data, pipe, model) mesh: each stage's weights shard over ``model``
    inside the pipeline shard_map (Megatron column/row with explicit
    psums — :func:`_block_forward_tp`).  Mutually exclusive with
    sequence parallel.
    """

    def __init__(
        self,
        loader: Loader,
        *,
        vocab: int,
        d_model: int = 64,
        n_layers: int = 2,
        n_heads: int = 4,
        d_ff: Optional[int] = None,  # FFN/expert hidden size (default 4*d)
        max_epochs: int = 10,
        hyper: Optional[optimizer.HyperParams] = None,
        attention: str = "auto",  # "dot" | "flash" | "auto"
        # "bf16": q/k/v cast to bf16 at the attention boundary — the MXU
        # dots run bf16 with f32 accumulation (measured 1.2-1.5x on v5e);
        # params/activations/softmax stay f32
        attention_dtype: str = "f32",  # "f32" | "bf16"
        remat: bool = False,  # jax.checkpoint each block (long context)
        moe_experts: int = 0,  # >1: MoE FFN per block (ops/moe.py)
        moe_top_k: int = 1,
        moe_dispatch: str = "dense",  # "dense" | "capacity"
        sequence_parallel: bool = False,
        tensor_parallel: bool = False,
        pipeline_parallel: bool = False,
        pipeline_microbatches: Optional[int] = None,
        mesh=None,
        decision: Optional[Decision] = None,
        snapshotter: Optional[Snapshotter] = None,
        lr_policy=None,
        parallel=None,
        prefetch_batches: int = 2,
        epoch_sync: str = "sync",
        recovery=None,
        rand_name: str = "default",
        name: str = "TransformerLMWorkflow",
    ):
        class _LM:
            params: list = []
            hyper: list = []

        super().__init__(
            loader,
            _LM(),
            loss_function="mse",  # metric label only; we override the step
            target="labels",
            decision=decision or Decision(metric="loss", max_epochs=max_epochs),
            snapshotter=snapshotter,
            lr_policy=lr_policy,
            parallel=parallel,
            prefetch_batches=prefetch_batches,
            epoch_sync=epoch_sync,
            recovery=recovery,
            name=name,
        )
        self.vocab = vocab
        self.d_model = d_model
        self.n_layers = n_layers
        self.n_heads = n_heads
        self.d_ff = d_ff
        self.hyper = hyper or optimizer.HyperParams(
            learning_rate=0.1, gradient_moment=0.9
        )
        self.rand_name = rand_name
        self.attention = attention
        if attention_dtype not in ("f32", "bf16"):
            raise ValueError(
                f"attention_dtype={attention_dtype!r}: want 'f32' or 'bf16'"
            )
        self.attention_dtype = attention_dtype
        self.remat = remat
        self.moe_experts = moe_experts
        self.moe_top_k = moe_top_k
        self.moe_dispatch = moe_dispatch
        if moe_experts > 1 and pipeline_parallel and tensor_parallel:
            # manual EP inside the pipeline shard_map: experts shard over
            # the model axis (apply_local_shard + the stage psum); only
            # dense dispatch has the manual formulation
            if moe_dispatch != "dense":
                raise ValueError(
                    "pipeline+tensor parallel MoE supports only "
                    "moe_dispatch='dense' (experts shard over the model "
                    "axis with a manual combine psum; capacity dispatch "
                    "has no manual-EP formulation here)"
                )
        self.sequence_parallel = sequence_parallel
        self.tensor_parallel = tensor_parallel
        self.pipeline_parallel = pipeline_parallel
        self.mesh = mesh
        self.max_seq = int(loader.sample_shape[0])
        if pipeline_parallel:
            from znicz_tpu.parallel.mesh import PIPE_AXIS

            if sequence_parallel:
                raise ValueError(
                    "pipeline_parallel is mutually exclusive with "
                    "sequence parallel (both want to own the batch layout)"
                )
            if parallel is not None:
                # DPxPP(xTP): batch over data, stages over pipe (weights
                # additionally over model under TP), on ONE mesh — the
                # placement policy's mesh is the pipeline's mesh
                if mesh is not None and mesh != parallel.mesh:
                    raise ValueError(
                        "pipeline_parallel with parallel=DataParallel: "
                        "pass the (data, pipe) mesh via the DataParallel "
                        "(mesh= must be omitted or identical)"
                    )
                mesh = self.mesh = parallel.mesh
                from znicz_tpu.parallel import DataParallel

                if self.parallel.param_rules is None:
                    self.parallel = DataParallel(
                        parallel.mesh,
                        param_rules=(
                            lm_pp_tp_rules if tensor_parallel else lm_pp_rules
                        ),
                    )
            if mesh is None or PIPE_AXIS not in mesh.shape:
                raise ValueError(
                    "pipeline_parallel=True needs a mesh with a 'pipe' axis"
                )
            if tensor_parallel:
                n_model = mesh.shape.get(MODEL_AXIS, 1)
                if n_model <= 1:
                    raise ValueError(
                        "pipeline+tensor parallel needs a mesh with a "
                        "'model' axis > 1"
                    )
                if n_heads % n_model:
                    raise ValueError(
                        f"n_heads={n_heads} not divisible by model axis "
                        f"{n_model}"
                    )
                if moe_experts > 1 and moe_experts % n_model:
                    raise ValueError(
                        f"moe_experts={moe_experts} not divisible by model "
                        f"axis {n_model} (experts shard over it under "
                        "pipeline+tensor parallel)"
                    )
                if self.parallel is None:
                    raise ValueError(
                        "pipeline+tensor parallel needs parallel="
                        "DataParallel over the (data, pipe, model) mesh "
                        "(stage weight placement rides its param_rules)"
                    )
            self._n_stages = mesh.shape[PIPE_AXIS]
            if n_layers % self._n_stages:
                raise ValueError(
                    f"n_layers={n_layers} not divisible by pipe axis "
                    f"{self._n_stages}"
                )
            # 6 microbatches per stage bounds the GPipe bubble
            # (S-1)/(S-1+M) under 1/7 ~ 0.143 for EVERY stage count —
            # S alone cooks in up to 43%.  The default clamps to the
            # largest batch divisor <= 6S so existing minibatch sizes keep
            # working; an EXPLICIT microbatch count is validated strictly
            # in pipeline_apply instead of silently adjusted.
            if pipeline_microbatches:
                self.pipeline_microbatches = pipeline_microbatches
            else:
                # under DPxPP the microbatch rows must also split over the
                # data axis, so the search wants bs % m == 0 AND
                # (bs // m) % n_data == 0 — m=1 always satisfies both
                # (multi-host/DP already require n_data | bs)
                bs = loader.max_minibatch_size
                n_data = (
                    self.parallel.n_data if self.parallel is not None else 1
                )
                m = min(6 * self._n_stages, bs)
                while m > 1 and (bs % m or (bs // m) % n_data):
                    m -= 1
                if bs % m or (bs // m) % n_data:
                    raise ValueError(
                        f"no pipeline microbatch count divides batch {bs} "
                        f"into data-axis-{n_data}-divisible microbatches; "
                        "choose minibatch_size as a multiple of n_data"
                    )
                self.pipeline_microbatches = m
                from znicz_tpu.parallel.pipeline import bubble_fraction

                bubble = bubble_fraction(self._n_stages, m)
                if bubble > 0.16:  # the documented default bound
                    self.warning(
                        "auto-selected %d pipeline microbatches (batch %d, "
                        "data axis %d) leaves a GPipe bubble of %.0f%%; "
                        "raise minibatch_size toward %d*n_data to recover "
                        "pipeline efficiency",
                        m, bs, n_data, 100 * bubble,
                        6 * self._n_stages,
                    )
        if tensor_parallel and not pipeline_parallel:
            from znicz_tpu.parallel import DataParallel

            if not isinstance(self.parallel, DataParallel):
                raise ValueError(
                    "tensor_parallel=True needs parallel=DataParallel(mesh) "
                    "with a model axis"
                )
            n_model = self.parallel.mesh.shape.get(MODEL_AXIS, 1)
            if n_model <= 1:
                raise ValueError(
                    "tensor_parallel=True but the mesh's model axis is 1"
                )
            if n_heads % n_model:
                raise ValueError(
                    f"n_heads={n_heads} not divisible by model axis {n_model}"
                )
            if self.parallel.param_rules is None:
                # never mutate the caller's DataParallel (it may be shared
                # with workflows whose params want the size heuristic —
                # lm_tp_rules replicates everything it doesn't recognize)
                self.parallel = DataParallel(
                    self.parallel.mesh,
                    tp=self.parallel.tp,
                    tp_min_features=self.parallel.tp_min_features,
                    param_rules=lm_tp_rules,
                )

    def _batch_target(self, mb):
        return np.zeros(len(mb.mask), np.int32)  # unused host-side dummy

    def generate(
        self,
        prompt,
        *,
        max_new_tokens: int,
        eos_id: Optional[int] = None,
        temperature: float = 0.0,
        top_k: int = 0,
        top_p: float = 1.0,
        rng=None,
    ):
        """KV-cache autoregressive generation from the CURRENT trained
        params (:mod:`znicz_tpu.workflow.generate`); returns
        [B, Tp + max_new_tokens] tokens, prompt included.  Greedy at
        ``temperature=0``; with ``eos_id`` the decode loop exits once
        every row has emitted EOS (rows pad the rest of the budget with
        it).  Non-pipelined params only (the pipelined
        stacked-stage layout trains; export/decode from a non-pipelined
        run, like ``export_lm_model``).  Decode attention runs f32
        regardless of ``attention_dtype`` — that knob is a training-
        throughput lever; decode logits golden-match the f32
        ``lm_apply``."""
        if self.pipeline_parallel:
            raise ValueError(
                "generate() wants the flat [embed, blocks..., head] param "
                "layout; pipelined (stacked-stage) params are train-only — "
                "decode from a non-pipelined workflow"
            )
        if self.state is None:
            self.initialize()
        from znicz_tpu.workflow.generate import generate as _generate

        return _generate(
            self.state.params,
            jnp.asarray(prompt, jnp.int32),
            n_heads=self.n_heads,
            max_new_tokens=max_new_tokens,
            eos_id=eos_id,
            temperature=temperature,
            top_k=top_k,
            top_p=top_p,
            rng=rng,
            moe_top_k=self.moe_top_k,
            moe_dispatch=self.moe_dispatch,
        )

    def _sharded_flash(self):
        """Flash kernel under DataParallel: a pallas_call has no GSPMD
        partitioning rule, but batch-heads are embarrassingly parallel — a
        ``shard_map`` over the data (and, under TP, model/head) axis runs
        the kernel per-shard and composes with the GSPMD-sharded step."""
        from jax.sharding import PartitionSpec as P

        from znicz_tpu.ops.pallas.attention import flash_attention
        from znicz_tpu.parallel.mesh import DATA_AXIS

        mesh = self.parallel.mesh
        shard_heads = (
            self.tensor_parallel and mesh.shape.get(MODEL_AXIS, 1) > 1
        )
        spec = P(DATA_AXIS, None, MODEL_AXIS if shard_heads else None, None)

        def fn(q, k, v, *, causal=False, scale=None):
            return jax.shard_map(
                partial(flash_attention, causal=causal, scale=scale),
                mesh=mesh,
                in_specs=(spec, spec, spec),
                out_specs=spec,
                check_vma=False,  # pallas out_shape carries no vma info
            )(q, k, v)

        return fn

    def _attention_fn(self):
        fn = self._attention_fn_base()
        if self.attention_dtype != "bf16":
            return fn
        from znicz_tpu.ops import attention as att_op

        base_fn = fn or att_op.dot_product_attention

        def bf16_fn(q, k, v, **kw):
            # cast at the boundary only: scores/softmax/accumulation stay
            # f32 inside the kernel (or via preferred_element_type in the
            # jnp twin); the output returns to the residual dtype
            return base_fn(
                q.astype(jnp.bfloat16),
                k.astype(jnp.bfloat16),
                v.astype(jnp.bfloat16),
                **kw,
            ).astype(q.dtype)

        return bf16_fn

    def _attention_fn_base(self):
        on_tpu = backend.on_tpu()
        if self.sequence_parallel:
            from znicz_tpu.parallel.ring_attention import ring_attention

            # ring attention owns the sequence axis; its per-shard inner
            # blocks run the flash kernel when requested (or on TPU by
            # default), so SP long context runs at kernel speed
            inner = (
                "flash"
                if self.attention == "flash"
                or (self.attention == "auto" and on_tpu
                    and self.max_seq >= 512)  # same gate as non-SP auto
                else "dense"
            )
            return partial(ring_attention, mesh=self.mesh, inner=inner)
        # blockwise flash kernel (ops/pallas/attention.py): O(T·D) memory
        # and VMEM-resident online softmax — the long-context default on
        # TPU once the quadratic score matrix stops being a rounding error
        if self.attention == "flash" or (
            self.attention == "auto" and on_tpu and self.max_seq >= 512
        ):
            # under PP the kernel already runs inside the pipe/data
            # shard_map (per-device code) — only the GSPMD-sharded
            # non-pipelined step needs the explicit wrapper
            if self.parallel is not None and not self.pipeline_parallel:
                return self._sharded_flash()
            from znicz_tpu.ops.pallas.attention import flash_attention

            return flash_attention
        return None

    def _build_steps(self):
        n_heads = self.n_heads
        attention_fn = self._attention_fn()

        if self.pipeline_parallel:
            from znicz_tpu.parallel.mesh import DATA_AXIS

            apply_fn = partial(
                lm_apply_pipelined,
                n_heads=n_heads,
                mesh=self.mesh,
                n_microbatches=self.pipeline_microbatches,
                data_axis=DATA_AXIS if self.parallel is not None else None,
                tp_axis=MODEL_AXIS if self.tensor_parallel else None,
                attention_fn=attention_fn,
                remat=self.remat,
                moe_top_k=self.moe_top_k,
                moe_dispatch=self.moe_dispatch,
            )
        else:
            apply_fn = partial(
                lm_apply, n_heads=n_heads, attention_fn=attention_fn,
                remat=self.remat,
                moe_top_k=self.moe_top_k,
                moe_dispatch=self.moe_dispatch,
            )

        def loss_metrics(params, tokens, mask):
            tokens = tokens.astype(jnp.int32)
            logits = apply_fn(params, tokens)
            # next-token CE: predict tokens[:, 1:] from positions [:-1].
            # Fused formulation nll = logsumexp(logits) - logits[target]:
            # never materializes the [B, T, V] log-softmax array that the
            # textbook log_softmax+gather form writes and re-reads (and
            # re-reads again for argmax) — measured 1.32x on the whole
            # train step for a 50M-param LM at T=2048 on v5e.  Same math.
            lg = logits[:, :-1]
            tgt = tokens[:, 1:]
            lse = jax.nn.logsumexp(lg, axis=-1)
            tgt_logit = jnp.take_along_axis(
                lg, tgt[..., None], axis=-1
            )[..., 0]
            nll = lse - tgt_logit
            per_sample = jnp.mean(nll, axis=1)  # [B]
            n_valid = jnp.maximum(jnp.sum(mask), 1.0)
            loss = jnp.sum(per_sample * mask) / n_valid
            pred = jnp.argmax(lg, axis=-1)  # == argmax of log_softmax
            acc = jnp.sum(
                jnp.mean((pred == tgt).astype(jnp.float32), axis=1) * mask
            ) / n_valid
            return loss, {
                "loss": loss,
                "n_samples": n_valid,
                "n_err": jnp.zeros((), jnp.int32),
                "token_accuracy": acc,
            }

        def train_step(state: TrainState, x, y, mask, lr_scale):
            grads, metrics = jax.grad(loss_metrics, has_aux=True)(
                state.params, x, mask
            )
            # anomaly-watch input; popped before the epoch accumulator
            metrics = dict(metrics, grad_norm=_global_norm(grads))
            hyper = self.hyper._replace(
                learning_rate=self.hyper.learning_rate * lr_scale,
                learning_rate_bias=(
                    None
                    if self.hyper.learning_rate_bias is None
                    else self.hyper.learning_rate_bias * lr_scale
                ),
            )
            if self.pipeline_parallel:  # dict-of-stacked-stages pytree
                new_p, new_v = optimizer.update_pytree(
                    state.params, grads, state.velocity, hyper
                )
            else:
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
            _, metrics = loss_metrics(params, x, mask)
            return metrics

        self._finalize_steps(
            train_step,
            eval_step,
            ["loss", "n_samples", "n_err", "token_accuracy"],
        )

    def _create_initial_state(self) -> TrainState:
        params = init_lm_params(
            self.vocab,
            self.d_model,
            self.n_layers,
            self.n_heads,
            self.max_seq,
            d_ff=self.d_ff,
            moe_experts=self.moe_experts,
            rand_name=self.rand_name,
        )
        if self.pipeline_parallel:
            params = stack_lm_blocks(params, self._n_stages)
            if self.parallel is None:
                from znicz_tpu.parallel.pipeline import shard_stacked_params

                # stage params chunk-per-device up front; embed/head stay
                # replicated (GSPMD propagates through the update); with a
                # placement policy, shard_state's lm_pp_rules do this
                params["stages"] = shard_stacked_params(
                    params["stages"], self.mesh
                )
        return TrainState.create(params, prng.get("workflow").key())
