"""KV-cache autoregressive decoding for the transformer LM.

The reference deploys every model through export + a native forward engine
(SURVEY.md 2.4 libZnicz); the flagship LM additionally needs the other half
of its lifecycle — incremental decoding.  Re-founded TPU-first: the KV cache
is a STATIC-shape [B, T_max, H, hd] buffer per block (XLA wants fixed
shapes; validity is an index mask, not a dynamic length), each decode step
is one position through the block tower (``jax.lax.dynamic_update_slice``
into the cache, attention over the full buffer masked to ``<= pos``), and
the whole generation loop is ONE ``lax.while_loop`` — a single compiled
program, no per-token dispatch, that exits as soon as every row has hit
the EOS id (or the budget).

Numerics match :func:`znicz_tpu.workflow.transformer.lm_apply` exactly
(same projection/attention formulation, f32 accumulation), which the golden
tests assert position-by-position.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from znicz_tpu.ops.attention import paged_attention
from znicz_tpu.ops.normalization import layer_norm
from znicz_tpu.workflow.transformer import _block_ffn


def init_kv_cache(params, batch: int, max_seq: int, *, n_heads: int):
    """Zeroed [B, T_max, H, hd] K/V buffers, one pair per block."""
    caches = []
    for block in params[1:-1]:
        inner = block["wq"].shape[1]
        head_dim = inner // n_heads
        shape = (batch, max_seq, n_heads, head_dim)
        dtype = block["wq"].dtype
        caches.append(
            {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
        )
    return caches


def _block_step(
    block, x, cache, offset, *, n_heads, moe_top_k=1, moe_dispatch="dense",
):
    """One pre-LN block over ``x`` [B, Tq, D] at absolute positions
    ``offset .. offset+Tq-1``, reading/writing the KV cache.  Tq is the
    prompt length during prefill and 1 during decode — one definition for
    both, so they cannot drift from each other (and the attention math
    mirrors ``ops.attention.mha`` + ``dot_product_attention``: f32 score
    accumulation, stable softmax)."""
    b, tq, _ = x.shape
    h = layer_norm(x, block["ln1_scale"], block["ln1_bias"])

    def proj(w):
        y = jnp.dot(h, w, preferred_element_type=jnp.float32).astype(h.dtype)
        return y.reshape(b, tq, n_heads, -1)

    q, k_new, v_new = proj(block["wq"]), proj(block["wk"]), proj(block["wv"])
    k_cache = jax.lax.dynamic_update_slice(cache["k"], k_new, (0, offset, 0, 0))
    v_cache = jax.lax.dynamic_update_slice(cache["v"], v_new, (0, offset, 0, 0))
    t_max = k_cache.shape[1]
    # np.sqrt of a STATIC shape is a trace-time constant, not a host
    # effect (the project-wide pass sees this helper as traced)
    scale = 1.0 / np.sqrt(q.shape[-1])  # znicz-check: disable=ZNC002
    s = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k_cache, preferred_element_type=jnp.float32
    ) * scale
    # causal validity by ABSOLUTE index: key position <= query position
    # (unwritten cache slots are > offset+Tq-1, so they mask out too)
    k_idx = jnp.arange(t_max)[None, None, None, :]
    q_idx = offset + jnp.arange(tq)[None, None, :, None]
    s = jnp.where(k_idx <= q_idx, s, -jnp.inf)
    p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    p = p / jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum(
        "bhqk,bkhd->bqhd", p.astype(v_cache.dtype), v_cache,
        preferred_element_type=jnp.float32,
    ).astype(x.dtype)
    o = o.reshape(b, tq, -1)
    x = x + jnp.dot(
        o, block["wo"], preferred_element_type=jnp.float32
    ).astype(x.dtype)
    h = layer_norm(x, block["ln2_scale"], block["ln2_bias"])
    x = x + _block_ffn(
        block, h, moe_top_k=moe_top_k, moe_dispatch=moe_dispatch
    )
    return x, {"k": k_cache, "v": v_cache}


def _embed_at(embed, tokens, offset):
    """Token + positional embedding for tokens [B, Tq] at ``offset``."""
    tq = tokens.shape[1]
    pos = jax.lax.dynamic_slice_in_dim(embed["pos"], offset, tq, axis=0)
    return embed["embed"][tokens] + pos[None, :, :]


def prefill(
    params, tokens, caches, *, n_heads, moe_top_k=1, moe_dispatch="dense",
):
    """Run the prompt [B, Tp] through the tower, filling positions
    ``0..Tp-1`` of the caches; returns (caches, last-position logits)."""
    x = _embed_at(params[0], tokens, 0)
    new_caches = []
    for block, cache in zip(params[1:-1], caches):
        x, cache = _block_step(
            block, x, cache, 0, n_heads=n_heads, moe_top_k=moe_top_k,
            moe_dispatch=moe_dispatch,
        )
        new_caches.append(cache)
    return new_caches, x[:, -1] @ params[-1]["head"]


def decode_step(
    params, caches, token, pos, *, n_heads, moe_top_k=1,
    moe_dispatch="dense",
):
    """One incremental step: ``token`` [B] at position ``pos`` -> (caches,
    next-position logits [B, vocab])."""
    x = _embed_at(params[0], token[:, None], pos)
    new_caches = []
    for block, cache in zip(params[1:-1], caches):
        x, cache = _block_step(
            block, x, cache, pos, n_heads=n_heads, moe_top_k=moe_top_k,
            moe_dispatch=moe_dispatch,
        )
        new_caches.append(cache)
    return new_caches, x[:, 0] @ params[-1]["head"]


# ---------------------------------------------------------------------------
# Paged KV cache (vLLM/PagedAttention lineage, docs/SERVING.md): K/V live
# in a shared [n_blocks, block_size, H*hd] pool per layer and each row
# owns an ordered block table — block-granular allocation instead of a
# dense [B, T_max] reservation per slot, so memory scales with the tokens
# actually decoded and the pool's free blocks ARE the concurrency budget.
# Heads are MERGED in storage and split on the query side, after the
# gather: a minor [block_size, H*hd] fills the TPU's (8, 128) tiles where
# [H, hd] fits none and made every program re-tile whole pools and
# windows (tests/test_paged_layout_aot.py).

NULL_BLOCK = 0  # reserved pool block: write target for idle/done rows


@dataclasses.dataclass(frozen=True)
class CacheKind:
    """One kind of cached state a paged tower keeps, and how long a row
    needs it.  A tower that mixes kinds of layers lists one of these a
    kind (``model.cache_kinds``); the engine then keeps pools' blocks, a
    free list and a block table a row FOR EACH, and hands the tower's
    programs ``{name: table}``.  ``window`` None: every token of a live
    row stays (table entry ``j`` covers positions ``j * block_size ..``).
    ``window`` W: a layer of this kind attends the last ``W`` keys, the
    query's own among them, so the engine gives back every block that lies
    wholly behind them and the table is a RING: the block that covers
    absolute block index ``b`` sits at entry ``b % width``."""

    name: str
    window: Optional[int] = None


def init_paged_kv(params, n_blocks: int, block_size: int):
    """Zeroed ``[n_blocks, block_size, H*hd]`` K/V pools (heads merged,
    see above), one pair per block of the tower.  Pool block
    ``NULL_BLOCK`` (index 0) is reserved as the null write target —
    allocators must hand out ``1..n_blocks-1`` — so rows with nothing to
    say (done, idle slot) can always write somewhere harmless instead of
    branching."""
    if n_blocks < 2 or block_size < 1:
        raise ValueError(
            f"want n_blocks >= 2 (one is the reserved null block) and "
            f"block_size >= 1; got {n_blocks}, {block_size}"
        )
    pools = []
    for block in params[1:-1]:
        shape = (n_blocks, block_size, block["wq"].shape[1])
        dtype = block["wq"].dtype
        pools.append(
            {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
        )
    return pools


def _paged_block_step(
    block, x, pool, write, tables, q_pos, *, n_heads, block_size,
    moe_top_k=1, moe_dispatch="dense",
):
    """One pre-LN block over ``x`` [B, Tq, D] with paged KV: ``write``
    scatters this layer's new K/V, heads merged as the pool stores them,
    into the pool (the caller resolves block ids once — the same indices
    serve every layer) and attention gathers through the block table
    (:func:`ops.attention.paged_attention` — same masked stable-softmax
    numerics as the dense :func:`_block_step`, asserted by the paged
    goldens)."""
    b, tq, _ = x.shape
    h = layer_norm(x, block["ln1_scale"], block["ln1_bias"])

    def proj(w):
        return jnp.dot(h, w, preferred_element_type=jnp.float32).astype(
            h.dtype
        )

    k_pool = write(pool["k"], proj(block["wk"]))
    v_pool = write(pool["v"], proj(block["wv"]))
    o = paged_attention(
        proj(block["wq"]).reshape(b, tq, n_heads, -1), k_pool, v_pool,
        tables, q_pos, block_size=block_size,
    )
    o = o.reshape(b, tq, -1)
    x = x + jnp.dot(
        o, block["wo"], preferred_element_type=jnp.float32
    ).astype(x.dtype)
    h = layer_norm(x, block["ln2_scale"], block["ln2_bias"])
    x = x + _block_ffn(
        block, h, moe_top_k=moe_top_k, moe_dispatch=moe_dispatch
    )
    return x, {"k": k_pool, "v": v_pool}


def paged_prefill_chunk(
    params, pools, table, tokens, offset, *, n_heads, block_size,
    last=None, moe_top_k=1, moe_dispatch="dense",
):
    """Process ONE aligned chunk of a single prompt through the tower,
    writing its K/V into the row's blocks; returns ``(pools, logits)``
    at the chunk's ``last`` position (its final position by default).

    ``tokens`` is ``[1, C]`` with ``C == block_size`` and ``offset`` a
    multiple of ``block_size`` — the chunk occupies exactly one block,
    so the write is one whole-block scatter and the compiled program has
    a SINGLE shape regardless of prompt length (chunked prefill's whole
    point: a long prompt is N invocations of this one program,
    interleavable with decode chunks, instead of one monolithic
    per-bucket prefill that stalls the batch).  ``table`` is the row's
    [M] block table.

    Prompts anchor at position 0 and the FINAL chunk is RIGHT-padded to
    the block boundary (prefix-cache alignment: a shared prefix fills
    identical block contents whatever the full prompt's length — a left
    pad would shift every block by ``-len % block_size`` and kill
    sharing).  ``last`` (traced) is the in-chunk index of the prompt's
    last real token, so the returned logits are the first-token logits
    even when the tail of the chunk is pad.  The pad positions DO write
    (garbage) K/V at absolute positions past the prompt, but validity
    is by absolute index — no query ever attends a position it hasn't
    reached — and incremental decode overwrites each pad slot before
    its position becomes visible."""
    c = tokens.shape[1]
    if c != block_size:
        raise ValueError(
            f"chunk length {c} must equal block_size {block_size} "
            "(one chunk == one block)"
        )
    blk = table[offset // block_size]
    x = _embed_at(params[0], tokens, offset)
    q_pos = offset + jnp.arange(c)[None, :]

    def write(pool, new):
        return pool.at[blk].set(new[0])

    new_pools = []
    for block, pool in zip(params[1:-1], pools):
        x, pool = _paged_block_step(
            block, x, pool, write, table[None], q_pos, n_heads=n_heads,
            block_size=block_size, moe_top_k=moe_top_k,
            moe_dispatch=moe_dispatch,
        )
        new_pools.append(pool)
    if last is None:
        xl = x[:, -1]
    else:
        xl = jax.lax.dynamic_index_in_dim(x, last, axis=1, keepdims=False)
    return new_pools, xl @ params[-1]["head"]


def copy_paged_block(pools, src, dst):
    """Copy pool block ``src`` into ``dst`` across every layer's pool
    (a ``k``/``v`` pair, or one array of latent rows: every array of a
    pool is ``[n_blocks, block_size, ...]``) — the copy-on-write split for paged prefix sharing: when a row
    must write into a block other tables (or the prefix cache) still
    reference, the engine allocates a fresh block, copies the shared
    content here, and retargets only its own table entry.  ``src`` and
    ``dst`` are traced operands, so one compiled program serves every
    split."""
    return [
        {name: a.at[dst].set(a[src]) for name, a in pool.items()}
        for pool in pools
    ]


def paged_decode_step(
    params, pools, tables, token, pos, *, n_heads, block_size,
    write_mask=None, moe_top_k=1, moe_dispatch="dense",
):
    """One incremental paged step: ``token`` [B] at PER-ROW positions
    ``pos`` [B] -> ``(pools, next logits [B, vocab])``.

    Each row writes its new K/V at ``(tables[b, pos_b // bs],
    pos_b % bs)`` — rows own disjoint blocks, so the batched scatter
    never collides — and attends through its own table.  Rows with
    ``write_mask`` False (done/idle slots) write to the reserved
    ``NULL_BLOCK`` instead, so a retired-but-still-carried row can
    never scribble into a block the allocator has handed to someone
    else.  Per-row positions are native here: the block table IS the
    indirection."""
    b = token.shape[0]
    rows = jnp.arange(b)
    blk = tables[rows, pos // block_size]
    if write_mask is not None:
        blk = jnp.where(write_mask, blk, NULL_BLOCK)
    slot = pos % block_size
    x = _embed_rows(params[0], token, pos)

    def write(pool, new):
        return pool.at[blk, slot].set(new[:, 0])

    new_pools = []
    for block, pool in zip(params[1:-1], pools):
        x, pool = _paged_block_step(
            block, x, pool, write, tables, pos[:, None], n_heads=n_heads,
            block_size=block_size, moe_top_k=moe_top_k,
            moe_dispatch=moe_dispatch,
        )
        new_pools.append(pool)
    return new_pools, x[:, 0] @ params[-1]["head"]


def _embed_rows(embed, token, pos):
    """Token + positional embedding at PER-ROW absolute positions (the
    paged twin of :func:`_embed_at`, which takes one shared offset).
    ``token``/``pos`` are ``[B]`` (one decode step) or ``[B, W]`` (a
    speculative verify chunk — W consecutive positions per row)."""
    if token.ndim == 1:
        token = token[:, None]
        pos = pos[:, None]
    pos = jnp.clip(pos, 0, embed["pos"].shape[0] - 1)
    return embed["embed"][token] + embed["pos"][pos]


def paged_verify_chunk(
    params, pools, tables, tokens, pos, *, n_heads, block_size,
    write_mask=None, moe_top_k=1, moe_dispatch="dense",
):
    """Score W tokens per row at per-row positions ``pos .. pos+W-1``
    through the paged tower in ONE forward pass — the speculative-
    decoding VERIFY primitive; returns ``(pools, logits [B, W, vocab])``
    where ``logits[:, i]`` is the next-token distribution AFTER input
    token ``i``.

    ``tokens`` is ``[B, W]``: each row's current last sampled token
    followed by its drafted continuation (padded past the draft).  Each
    position writes its K/V at ``(tables[b, (pos_b+i)//bs],
    (pos_b+i)%bs)`` before attention gathers through the table, so a
    query at position ``pos_b+i`` attends exactly what ``i`` sequential
    :func:`paged_decode_step` calls would have seen — same masked
    stable-softmax numerics, same validity-by-absolute-index contract,
    which is what makes greedy speculative decode token-identical to
    non-speculative decode.  ``write_mask`` ``[B, W]`` routes masked
    positions (done rows, positions past the row's budget — whose
    table lookup may even fall off the windowed table) to the reserved
    ``NULL_BLOCK``.  Rejected positions DO leave garbage K/V behind;
    that is safe for the same reason prefill's right-pad is: validity
    is by absolute index, and the next step's writes overwrite every
    garbage position before any query can reach it — the engine
    additionally truncates the block table back to the accepted prefix
    (rollback is bookkeeping, not copies).  W, like the chunk length in
    :func:`paged_prefill_chunk`, is a compile-time shape: the engine
    snaps it to a small bucket ladder so accepted/drafted lengths are
    traced operands and no accepted length ever compiles a new
    program."""
    b, w = tokens.shape
    rows = jnp.arange(b)[:, None]
    pos_w = pos[:, None] + jnp.arange(w)[None, :]  # [B, W]
    blk = tables[rows, pos_w // block_size]
    if write_mask is not None:
        blk = jnp.where(write_mask, blk, NULL_BLOCK)
    slot = pos_w % block_size
    x = _embed_rows(params[0], tokens, pos_w)

    def write(pool, new):
        return pool.at[blk, slot].set(new)

    new_pools = []
    for block, pool in zip(params[1:-1], pools):
        x, pool = _paged_block_step(
            block, x, pool, write, tables, pos_w, n_heads=n_heads,
            block_size=block_size, moe_top_k=moe_top_k,
            moe_dispatch=moe_dispatch,
        )
        new_pools.append(pool)
    return new_pools, x @ params[-1]["head"]


# ---------------------------------------------------------------------------
# Speculative drafting (Leviathan et al. 2023 lineage).  The drafter is
# a tiny HOST-side interface — ``propose(context, k) -> up to k token
# ids`` — so the paged engine's verify path is agnostic to where the
# guesses come from: prompt-lookup below costs zero extra weights; a
# draft-model drafter (a small transformer_lm sharing the target's
# tokenizer) plugs into the same hook.

# verify-width (k+1) bucket ladder: drafted lengths snap UP a rung so
# the verify program compiles once per rung, never per accepted length
DEFAULT_SPEC_BUCKETS = (2, 4, 8)


class PromptLookupDrafter:
    """Prompt-lookup / n-gram drafting (Saxena 2023): propose the
    continuation of the MOST RECENT earlier occurrence of the context's
    final n-gram, longest n first.  The context is the row's own prompt
    plus everything it has emitted — repetitive prompts (retrieval,
    code, multi-turn chat) and self-repeating generations both draft
    well, and the proposal costs a few numpy comparisons, no weights.

    Duck-typed drafter contract (what :class:`~znicz_tpu.services
    .engine.PagedDecodeEngine` calls every speculative tick, per
    decoding row): ``propose(context, k)`` takes the 1-D int32 token
    context and returns UP TO ``k`` proposed next tokens (empty when it
    has no confident guess — the engine then falls back to the plain
    decode chunk, so an unpredictable stream never pays verify
    overhead).  ``ngram_min=2`` by default: a 1-gram match is noise on
    most streams, and a wasted verify pass costs real tower compute
    where an abstained tick costs nothing."""

    def __init__(self, ngram_max: int = 3, ngram_min: int = 2):
        if ngram_min < 1 or ngram_max < ngram_min:
            raise ValueError(
                f"want 1 <= ngram_min <= ngram_max; got "
                f"{ngram_min}, {ngram_max}"
            )
        self.ngram_max = int(ngram_max)
        self.ngram_min = int(ngram_min)

    def propose(self, context, k: int) -> np.ndarray:
        ctx = np.asarray(context, np.int32).reshape(-1)
        if k <= 0:
            return np.zeros((0,), np.int32)
        for n in range(self.ngram_max, self.ngram_min - 1, -1):
            if ctx.size <= n:
                continue
            pattern = ctx[-n:]
            win = np.lib.stride_tricks.sliding_window_view(ctx, n)
            hits = np.nonzero((win == pattern).all(axis=1))[0]
            # need at least one continuation token; this also drops the
            # terminal self-match (the pattern matching itself)
            hits = hits[hits + n < ctx.size]
            if hits.size:
                # prefer the LATEST occurrence with k continuation
                # tokens available: inside a repeated run the most
                # recent match sits one step from the end and could
                # only ever propose a single token, while an earlier
                # occurrence of the same pattern carries the whole
                # periodic continuation (the continuation may overlap
                # the context tail — that IS the periodic guess)
                full = hits[hits + n + int(k) <= ctx.size]
                i = int(full[-1] if full.size else hits[-1])
                return ctx[i + n: i + n + int(k)].copy()
        return np.zeros((0,), np.int32)


def _filter_logits(logits, temperature, top_k, nucleus, top_p):
    """The sampling truncation pipeline: temperature scaling, optional
    ``top_k`` cut (lax.top_k wants a static k) and optional ``top_p``
    nucleus (smallest prefix of the sorted distribution with cumulative
    probability >= top_p; the argmax token is always kept).  Operates
    on the LAST axis, so it serves ``[B, vocab]`` decode logits and
    ``[B, W, vocab]`` speculative verify logits alike — the ONE owner
    of the truncation semantics, shared by :func:`_sample` and the
    verify program's rejection sampler (the accept probability must be
    computed on exactly the distribution :func:`_sample` draws from)."""
    logits = logits / temperature
    if top_k:
        kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if nucleus:
        sl = jnp.sort(logits, axis=-1)[..., ::-1]  # descending
        probs = jax.nn.softmax(sl, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        keep = (cum - probs) < top_p  # mass BEFORE the token; [..., 0] True
        thr = jnp.min(
            jnp.where(keep, sl, jnp.inf), axis=-1, keepdims=True
        )
        logits = jnp.where(logits >= thr, logits, -jnp.inf)
    return logits


def _sample(logits, key, temperature, top_k, nucleus, top_p):
    """Greedy (``greedy`` static) or temperature sampling over the
    truncated distribution (:func:`_filter_logits`).  Only the
    STRUCTURAL knobs (top_k and the nucleus on/off flag) are trace-time
    constants; ``temperature`` and ``top_p`` are traced operands, so
    sweeping them never recompiles the decode program."""
    return jax.random.categorical(
        key, _filter_logits(logits, temperature, top_k, nucleus, top_p),
        axis=-1,
    ).astype(jnp.int32)


def _check_sampling_args(params, temperature, top_k, top_p, rng, eos_id):
    """Shared argument validation for generate() and the engine;
    returns (top_k, rng) with the full-support clamp and greedy
    dummy key applied."""
    if temperature < 0.0:
        raise ValueError(f"want temperature >= 0; got {temperature}")
    if temperature != 0.0 and rng is None:
        raise ValueError("temperature > 0 needs an rng key")
    if top_k < 0 or not 0.0 < top_p <= 1.0:
        raise ValueError(
            f"want top_k >= 0 and 0 < top_p <= 1; got {top_k}, {top_p}"
        )
    vocab = params[-1]["head"].shape[-1]
    if eos_id is not None and not 0 <= eos_id < vocab:
        raise ValueError(f"eos_id {eos_id} outside vocab {vocab}")
    if top_k >= vocab:
        top_k = 0  # full support — no truncation (mirrors moe's clamp)
    if rng is None:
        # only reachable in greedy mode (temperature != 0 raised above),
        # where the key is NEVER consumed — the loop just wants a
        # key-typed operand.  A registry draw here would advance (and
        # snapshot) a stream nothing reads; a fixed dummy is the honest
        # spelling, same pattern as ops/pallas/rbm.py.
        rng = jax.random.key(0)  # znicz-check: disable=ZNC004
    return top_k, rng


def generate(
    params,
    prompt: jnp.ndarray,  # [B, Tp] int32
    *,
    n_heads: int,
    max_new_tokens: int,
    eos_id: Optional[int] = None,
    temperature: float = 0.0,
    top_k: int = 0,
    top_p: float = 1.0,
    rng: Optional[jax.Array] = None,
    moe_top_k: int = 1,
    moe_dispatch: str = "dense",
):
    """Autoregressive generation; returns [B, Tp + max_new_tokens] tokens
    (prompt included).  ``temperature=0`` is greedy argmax; otherwise
    softmax sampling at the given temperature (``rng`` required),
    optionally truncated to the ``top_k`` highest logits and/or the
    ``top_p`` nucleus.  The decode loop is one ``lax.while_loop`` —
    per-token cost is one cached block-tower step, not a growing
    re-forward, and with ``eos_id`` set the loop EXITS as soon as every
    row has emitted EOS (rows that finish early emit ``eos_id`` for the
    rest of the budget, identical to the full-budget run up to EOS).
    ``temperature``/``top_p`` are traced operands: sweeping them reuses
    one compiled program (only greedy<->sampling, top_k, the nucleus
    on/off flag, ``eos_id`` and shapes recompile)."""
    if max_new_tokens < 1:
        raise ValueError(f"want max_new_tokens >= 1; got {max_new_tokens}")
    tp = prompt.shape[1]
    t_max = tp + max_new_tokens
    max_pos = params[0]["pos"].shape[0]
    if t_max > max_pos:
        raise ValueError(
            f"prompt {tp} + max_new_tokens {max_new_tokens} exceeds the "
            f"positional table ({max_pos}); re-init the LM with a larger "
            "max_seq"
        )
    top_k, rng = _check_sampling_args(
        params, temperature, top_k, top_p, rng, eos_id
    )
    return _generate_impl(
        params,
        jnp.asarray(prompt, jnp.int32),
        jnp.float32(temperature),
        jnp.float32(top_p),
        rng,
        n_heads=n_heads,
        max_new_tokens=max_new_tokens,
        greedy=temperature == 0.0,
        top_k=top_k,
        nucleus=top_p < 1.0,
        eos_id=eos_id,
        moe_top_k=moe_top_k,
        moe_dispatch=moe_dispatch,
    )


@partial(
    jax.jit,
    static_argnames=(
        "n_heads", "max_new_tokens", "greedy", "top_k", "nucleus",
        "eos_id", "moe_top_k", "moe_dispatch",
    ),
)
def _generate_impl(
    params, prompt, temperature, top_p, rng, *, n_heads, max_new_tokens,
    greedy, top_k, nucleus, eos_id, moe_top_k, moe_dispatch,
):
    """One compiled decode program: prefill + a while_loop over decode
    steps carrying a per-row done-mask.  Per-step sampling keys are
    ``fold_in(rng, step)`` — derivable at any step index without
    materializing a presplit key array in the carry."""
    b, tp = prompt.shape
    t_max = tp + max_new_tokens

    def sample(logits, i):
        if greedy:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return _sample(
            logits, jax.random.fold_in(rng, i), temperature, top_k,
            nucleus, top_p,
        )

    caches = init_kv_cache(params, b, t_max, n_heads=n_heads)
    caches, logits = prefill(
        params, prompt, caches, n_heads=n_heads, moe_top_k=moe_top_k,
        moe_dispatch=moe_dispatch,
    )
    first = sample(logits, 0)
    fill = jnp.int32(eos_id if eos_id is not None else 0)
    out = jnp.full((b, max_new_tokens), fill, jnp.int32)
    out = jax.lax.dynamic_update_slice(out, first[:, None], (0, 0))
    if eos_id is not None:
        done = first == eos_id
    else:
        done = jnp.zeros((b,), bool)

    def cond(carry):
        _, _, i, done, _ = carry
        return (i < max_new_tokens) & ~jnp.all(done)

    def body(carry):
        caches, token, i, done, out = carry
        caches, logits = decode_step(
            params, caches, token, tp + i - 1, n_heads=n_heads,
            moe_top_k=moe_top_k, moe_dispatch=moe_dispatch,
        )
        nxt = sample(logits, i)
        if eos_id is not None:
            nxt = jnp.where(done, fill, nxt)
            done = done | (nxt == eos_id)
        out = jax.lax.dynamic_update_slice(out, nxt[:, None], (0, i))
        return (caches, nxt, i + 1, done, out)

    _, _, _, _, out = jax.lax.while_loop(
        cond, body, (caches, first, jnp.int32(1), done, out)
    )
    return jnp.concatenate([prompt, out], axis=1)


def bucket_for(n: int, ladder: Sequence[int]) -> int:
    """Smallest rung >= ``n``; past the top rung keep doubling it, so the
    ladder stays geometric and the compiled-program count logarithmic in
    the largest request ever seen."""
    if n <= 0:
        raise ValueError(f"want a positive length; got {n}")
    for rung in ladder:
        if n <= rung:
            return int(rung)
    rung = int(ladder[-1])
    while rung < n:
        rung *= 2
    return rung


def _params_fingerprint(params):
    """Hashable (treedef, shapes/dtypes) key component: one executable
    serves one parameter GEOMETRY (values may change, e.g. after more
    training — shapes may not)."""
    leaves, treedef = jax.tree_util.tree_flatten(params)
    return treedef, tuple(
        (tuple(leaf.shape), str(leaf.dtype)) for leaf in leaves
    )
