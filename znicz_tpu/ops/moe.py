"""Mixture-of-experts FC layer with expert parallelism.

NOT in the reference (pre-transformer framework) — a new capability
completing the DP/TP/PP/SP/EP set.  TPU-native formulation: DENSE dispatch —
every expert computes every token and a top-k one-hot gate masks the
combination.  That trades k/E of the FLOPs for zero scatter/gather and a
trivially shardable einsum: with the expert dim sharded over the mesh's
``model`` axis (see :func:`expert_sharding`), GSPMD turns the combine into a
psum over ICI — the expert-parallel all-to-all collapses into the one
collective TPUs do best.  For the small expert counts this framework targets
(4-16), dense dispatch is the right trade (scaling-book style reasoning:
MXU utilization beats saved FLOPs at these sizes).

``dispatch="capacity"`` is the mode that scales to many experts:
GShard-style capacity-bounded dispatch.  Each expert processes at most
``C = ceil(k*B/E * capacity_factor)`` tokens; routing stably sorts the
(token, choice) pairs by expert and scatter/gathers into the [E, C, F]
dispatch block, so expert FLOPs are ``k*B*capacity_factor*F*H`` and the
routing working set is O(B*k*F + E*C*F) — both independent of E (no
[B, E, C] one-hot tensors).  Tokens over capacity are dropped (output 0;
the residual layer wrapper passes them through unchanged — standard
token-drop accounting).  Slot priority is (choice rank, token index), so
results are deterministic.

``dispatch`` by :func:`held_experts_apply` is the DROPLESS mode of a model
with many small experts of which this chip holds a few (one share of an
expert-parallel layer): the router scores every published expert
(:func:`route_sigmoid_topk`), the (token, choice) pairs that chose an
expert held here are sorted by expert, and ONE grouped matrix product per
projection runs over exactly those rows (:func:`grouped_matmul`).  No
token is dropped whatever the imbalance, no expert computes a token that
did not choose it, and an expert nobody chose is not read.  What the
absent experts would have added is left out: that belongs to the other
chips' shares, and nothing here stands in for them or for the exchange.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from znicz_tpu.core import backend, prng
from znicz_tpu.ops.filling import fill


def init_params(
    n_input: int,
    n_hidden: int,
    n_experts: int,
    *,
    weights_stddev: Optional[float] = None,
    weights_filling: str = "gaussian",
    rand_name: str = "default",
    dtype=jnp.float32,
) -> Dict[str, jnp.ndarray]:
    gen = prng.get(rand_name)
    if weights_stddev is None:
        weights_stddev = 1.0 / np.sqrt(n_input)
    return {
        "router": jnp.asarray(
            fill(gen, (n_input, n_experts), weights_filling, weights_stddev),
            dtype,
        ),
        "w1": jnp.asarray(
            fill(
                gen, (n_experts, n_input, n_hidden),
                weights_filling, weights_stddev,
            ),
            dtype,
        ),
        "b1": jnp.zeros((n_experts, n_hidden), dtype),
        "w2": jnp.asarray(
            fill(
                gen, (n_experts, n_hidden, n_input),
                weights_filling, 1.0 / np.sqrt(n_hidden),
            ),
            dtype,
        ),
        "b2": jnp.zeros((n_experts, n_input), dtype),
    }


def apply(
    params: Dict[str, jnp.ndarray],
    x: jnp.ndarray,  # [B, F]
    *,
    top_k: int = 1,
    dispatch: str = "dense",
    capacity_factor: float = 1.25,
) -> jnp.ndarray:
    """Gated expert combination; returns [B, F] (residual-style output dim).

    Gate: softmax over the top-k router logits per token (renormalized),
    zero elsewhere.  ``dispatch``: "dense" (every expert runs every token;
    right for E <= ~4) or "capacity" (GShard-style capacity-bounded
    dispatch; expert FLOPs independent of E — the scaling mode).
    """
    logits = x @ params["router"]  # [B, E]
    e = logits.shape[-1]
    if dispatch == "capacity" and top_k < e:
        return _capacity_apply(
            params, x, logits, top_k=top_k, capacity_factor=capacity_factor
        )
    if dispatch not in ("dense", "capacity"):
        raise ValueError(f"unknown dispatch mode {dispatch!r}")
    if dispatch == "capacity":  # top_k >= e: capacity has no meaning
        import warnings

        warnings.warn(
            f"dispatch='capacity' with top_k={top_k} >= n_experts={e} "
            "degrades to the dense path (full softmax gates, no token "
            "drop); lower top_k for capacity semantics",
            stacklevel=2,
        )
    gates = _dense_gates(logits, top_k)
    out = jnp.einsum(
        "be,ebf->bf", gates, _dense_expert_outputs(params, x)
    )
    return out.astype(x.dtype)


def _dense_gates(logits: jnp.ndarray, top_k: int) -> jnp.ndarray:
    """[B, E] top-k gate matrix: softmax over the top-k logits per token
    (renormalized), zero elsewhere.  Shared by :func:`apply` and
    :func:`apply_local_shard` so the two dispatch paths cannot drift."""
    e = logits.shape[-1]
    if top_k >= e:
        return jax.nn.softmax(logits, axis=-1)
    # exact top-k membership via indices (a >=threshold mask would
    # activate EVERY tied expert — e.g. all of them for a zero row)
    top_vals, top_idx = jax.lax.top_k(logits, top_k)
    g = jax.nn.softmax(top_vals, axis=-1)  # [B, k]
    onehot = jax.nn.one_hot(top_idx, e, dtype=g.dtype)  # [B, k, E]
    return jnp.einsum("bk,bke->be", g, onehot)


def _dense_expert_outputs(params, x: jnp.ndarray) -> jnp.ndarray:
    """[E, B, F] every expert's (biased) output for every token — the
    dense-dispatch expert chain, shared by both dense paths."""
    h = jnp.einsum(
        "bf,efh->ebh", x, params["w1"], preferred_element_type=jnp.float32
    ) + params["b1"][:, None, :]
    h = jnp.tanh(h)
    return jnp.einsum(
        "ebh,ehf->ebf", h, params["w2"], preferred_element_type=jnp.float32
    ) + params["b2"][:, None, :]


def apply_local_shard(
    params_local: Dict[str, jnp.ndarray],
    x: jnp.ndarray,  # [B, F]
    *,
    top_k: int,
    shard_index,
) -> jnp.ndarray:
    """ONE expert shard's dense-dispatch contribution, for MANUAL expert
    parallelism inside a ``shard_map`` (the PPxTP stage forward, where
    GSPMD cannot insert the combine psum for us).

    ``params_local``'s expert leaves (w1/b1/w2/b2) hold this shard's
    ``E_local = E / n_shards`` contiguous experts; the router is
    REPLICATED, so the top-k gate over all ``E`` experts is computed
    identically on every shard and this shard weights only its own gate
    columns.  Gates partition over shards, so ``psum`` over the shard
    axis reproduces :func:`apply`'s dense dispatch exactly (b2 is
    gate-weighted per expert, so its partial sums correctly too).
    ``shard_index`` may be a traced ``jax.lax.axis_index``.
    """
    logits = x @ params_local["router"]  # [B, E] — router replicated
    e_local = params_local["w1"].shape[0]
    gates_local = jax.lax.dynamic_slice_in_dim(
        _dense_gates(logits, top_k), shard_index * e_local, e_local, axis=1
    )
    out = jnp.einsum(
        "be,ebf->bf", gates_local, _dense_expert_outputs(params_local, x)
    )
    return out.astype(x.dtype)


def expert_capacity(
    batch: int, n_experts: int, top_k: int, capacity_factor: float
) -> int:
    """Per-expert token budget C (static; shapes must be jit-constant)."""
    return max(1, int(np.ceil(top_k * batch / n_experts * capacity_factor)))


def _capacity_apply(params, x, logits, *, top_k, capacity_factor):
    """Sort/segment dispatch: working set O(B*k*F + E*C*F).

    No ``[B, E, C]`` one-hot tensors (at B=4096, E=64, cf=1.25 those are
    ~10^9 elements EACH — a memory wall exactly where capacity mode is
    supposed to take over).  Instead the (token, choice) pairs are stably
    sorted by expert; position-within-expert comes from a searchsorted
    against the segment starts, and dispatch/combine are a unique-slot
    scatter-add / gather.  Routing priority is (choice rank, token index),
    identical to the one-hot formulation: the flat order is choice-major
    and the sort is stable.  Gradients flow through gates, dispatched
    activations and expert outputs — the same differentiable paths as the
    einsum form (routing indices are non-differentiable in both)."""
    b, e = logits.shape
    f = x.shape[1]
    kb = top_k * b
    cap = expert_capacity(b, e, top_k, capacity_factor)
    top_vals, top_idx = jax.lax.top_k(logits, top_k)  # [B, k]
    g = jax.nn.softmax(top_vals, axis=-1)  # [B, k]
    # flatten choice-major (flat index = rank*B + token) so the stable
    # sort preserves (choice rank, token index) slot priority
    eid = top_idx.T.reshape(-1)  # [kB] expert of each choice
    tok = jnp.tile(jnp.arange(b, dtype=jnp.int32), top_k)  # [kB]
    gate = g.T.reshape(-1)  # [kB]
    order = jnp.argsort(eid, stable=True)
    eid_s = eid[order]
    # position inside the expert's capacity buffer = rank within segment
    first = jnp.searchsorted(eid_s, eid_s, side="left")
    pos_s = jnp.arange(kb, dtype=jnp.int32) - first.astype(jnp.int32)
    # over-capacity choices route to a trailing drop slot (row e*cap):
    # zero-initialized on dispatch, zero expert output on combine
    dest_s = jnp.where(pos_s < cap, eid_s * cap + pos_s, e * cap)
    xe = jnp.zeros((e * cap + 1, f), x.dtype)
    xe = xe.at[dest_s].add(x[tok[order]])  # unique slots: add == set
    xe = xe[:-1].reshape(e, cap, f)
    h = jnp.tanh(
        jnp.einsum(
            "ecf,efh->ech", xe, params["w1"],
            preferred_element_type=jnp.float32,
        )
        + params["b1"][:, None, :]
    )
    y = jnp.einsum(
        "ech,ehf->ecf", h, params["w2"], preferred_element_type=jnp.float32
    ) + params["b2"][:, None, :]
    y_flat = jnp.concatenate(
        [y.reshape(e * cap, f), jnp.zeros((1, f), y.dtype)]
    )
    contrib = y_flat[dest_s] * gate[order].astype(y.dtype)[:, None]
    out = jnp.zeros((b, f), y.dtype).at[tok[order]].add(contrib)
    return out.astype(x.dtype)


def expert_sharding(mesh, axis: str = "model"):
    """PartitionSpecs placing the expert dim on a mesh axis (EP).  The
    router stays replicated; all expert tensors shard on dim 0."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    def place(params):
        def put(name, leaf):
            spec = (
                P()
                if name == "router"
                else P(axis, *([None] * (leaf.ndim - 1)))
            )
            return jax.device_put(leaf, NamedSharding(mesh, spec))

        return {name: put(name, leaf) for name, leaf in params.items()}

    return place


# -- many small experts, a few of them held here ---------------------------

# (rows, contraction, columns) tile of the grouped product on the TPU: a
# decode step or a prefill chunk hands each expert a handful of rows, so
# the product streams weights and wants the widest weight tile that fits
GMM_TILING = (128, 1024, 1024)


def route_sigmoid_topk(
    h: jnp.ndarray,  # [T, D]
    router: jnp.ndarray,  # [D, E]
    *,
    top_k: int,
    scale: float = 1.0,
    normalize: bool = True,
    bias: Optional[jnp.ndarray] = None,  # [E] float32
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Sigmoid-scored routing over ALL ``E`` experts: ``(chosen [T, k]
    int32, weights [T, k] float32)`` with ``w = scale * s / sum(s)`` over
    the chosen (DeepSeek-V3 lineage, without its group limit).  Scores
    are float32.  ``bias`` (``topk_method: "noaux_tc"``) is added to the
    scores for the CHOICE only: the weights are the chosen experts'
    unbiased scores."""
    s = jax.nn.sigmoid(
        jnp.dot(h, router, preferred_element_type=jnp.float32)
    )
    if bias is None:
        top, idx = jax.lax.top_k(s, top_k)
    else:
        _, idx = jax.lax.top_k(s + bias, top_k)
        top = jnp.take_along_axis(s, idx, axis=-1)
    if normalize:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    return idx.astype(jnp.int32), top * scale


def route_softmax_topk(
    h: jnp.ndarray,  # [T, D]
    router: jnp.ndarray,  # [D, E]
    *,
    top_k: int,
    normalize: bool = True,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Softmax-scored routing over ALL ``E`` experts: ``(chosen [T, k]
    int32, weights [T, k] float32)``.  ``normalize`` takes the softmax
    over the chosen logits alone (the weights of a token sum to 1);
    without it the weights are the chosen experts' shares of the softmax
    over all ``E``.  Scores are float32."""
    logits = jnp.dot(h, router, preferred_element_type=jnp.float32)
    top, idx = jax.lax.top_k(logits, top_k)
    if normalize:
        weight = jax.nn.softmax(top, axis=-1)
    else:
        weight = jnp.exp(top - jax.nn.logsumexp(logits, axis=-1, keepdims=True))
    return idx.astype(jnp.int32), weight


def _tile(limit: int, dim: int) -> int:
    """The widest tile of whole 128-lane columns that is at most ``limit``
    and divides ``dim`` (the kernel masks the remainder of a tile that
    does not, on every visit); ``dim`` itself where it is narrower, and
    ``limit`` where nothing divides."""
    if dim <= limit:
        return dim
    for tile in range(limit - limit % 128, 0, -128):
        if dim % tile == 0:
            return tile
    return limit


def grouped_matmul(
    lhs: jnp.ndarray,  # [M, K], rows sorted by group
    rhs: jnp.ndarray,  # [G, K, N]
    group_sizes: jnp.ndarray,  # [G] int32, sum <= M
) -> jnp.ndarray:
    """``lhs[rows of group g] @ rhs[g]`` for every group, float32; rows
    past ``sum(group_sizes)`` belong to no group and hold NOTHING a caller
    may read (the TPU kernel never writes them).  On the TPU this is the
    megablox grouped-product kernel (a Pallas ``tpu_custom_call`` that
    visits only the (row tile, group) pairs that hold rows, so an empty
    group's weights are never fetched); elsewhere ``jax.lax.ragged_dot``."""
    if backend.on_tpu():
        from jax.experimental.pallas.ops.tpu.megablox import gmm

        m, k = lhs.shape
        n = rhs.shape[2]
        tiling = (
            min(GMM_TILING[0], m), _tile(GMM_TILING[1], k),
            _tile(GMM_TILING[2], n),
        )
        return gmm(
            lhs, rhs, group_sizes, preferred_element_type=jnp.float32,
            tiling=tiling,
        )
    return jax.lax.ragged_dot(
        lhs, rhs, group_sizes, preferred_element_type=jnp.float32
    )


def held_experts_apply(
    h: jnp.ndarray,  # [T, D] normalised rows
    chosen: jnp.ndarray,  # [T, k] expert ids over all E
    weight: jnp.ndarray,  # [T, k] float32
    gate: jnp.ndarray,  # [G, D, F]
    up: jnp.ndarray,  # [G, D, F]
    down: jnp.ndarray,  # [G, F, D]
    *,
    first_expert: int,
    row_mask: Optional[jnp.ndarray] = None,  # [T] bool
    activation=jax.nn.silu,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The part of ``sum_e w_e down_e(act(gate_e h) * up_e h)`` that the
    experts ``[first_expert, first_expert + G)`` give: ``(y [T, D]
    float32, pairs [G] int32)`` where ``pairs[g]`` counts the (token,
    choice) pairs expert ``g`` computed.  ``activation`` is the gate's
    (``silu``: SwiGLU; ``relu``: ReGLU).  Rows with ``row_mask`` False
    (idle slots, padding) are routed nowhere.  With ``first_expert`` 0
    and ``G`` the router's width every expert is held here and the part
    is the whole.

    Dropless sort dispatch: the ``T * k`` pairs are stably sorted by held
    expert (pairs of experts held elsewhere sort last, into no group), the
    tokens' rows are gathered in that order, three grouped products run
    over the groups, and the results are gathered back to ``[T, k]`` and
    summed under their weights.  Shapes are static at ``T * k`` rows, the
    work is not: the grouped product touches only the rows in a group."""
    t, k = chosen.shape
    g = gate.shape[0]
    local = chosen - first_expert
    held = (local >= 0) & (local < g)
    if row_mask is not None:
        held = held & row_mask[:, None]
    local = jnp.where(held, local, g).reshape(-1)
    order = jnp.argsort(local, stable=True)
    pairs = jnp.sum(
        local[:, None] == jnp.arange(g)[None, :], axis=0, dtype=jnp.int32
    )
    rows = h[order // k]  # [T * k, D], sorted by expert
    with jax.named_scope("moe_experts"):
        act = activation(
            grouped_matmul(rows, gate, pairs)
        ) * grouped_matmul(rows, up, pairs)
        out = grouped_matmul(act.astype(h.dtype), down, pairs)
    back = jnp.argsort(order).reshape(t, k)
    y = jnp.where(held[..., None], out[back] * weight[..., None], 0.0)
    return jnp.sum(y, axis=1), pairs
