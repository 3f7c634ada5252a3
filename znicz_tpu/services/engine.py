"""Continuous micro-batching decode engine: the LM serving front-end.

Orca-style continuous batching (PAPERS.md lineage) over a PAGED K/V
cache (:class:`PagedDecodeEngine`, PAPERS.md vLLM/Sarathi/RadixAttention
lineage; docs/SERVING.md): a request queue coalesces pending prompts
into a fixed B-slot batch; when a row retires (EOS or budget), its slot
is re-used by prefilling the next queued prompt into it while the other
rows keep decoding.  K/V live in a shared block pool (``[n_blocks,
block_size, H*hd]`` per layer: heads merged, so the minor dimensions
fill the TPU's (8, 128) tiles and no program re-tiles a pool —
tests/test_paged_layout_aot.py) and each slot owns a block table over
REFCOUNTED blocks — one table for every layer, or one for each KIND of
cached state where the tower declares kinds (window layers beside global
ones: :class:`_BlockKind`; the window kind gives blocks back while the
row lives).  Admission maps the longest prefix of the prompt
already in the content-hash PREFIX CACHE (chained block hashes — an
implicit radix structure; retiring and preempted requests publish their
completed full blocks) and chunk-prefills only the uncached tail; shared
blocks are read-only behind a copy-on-write guard.  Blocks are otherwise
allocated lazily as decode advances, prompts prefill in block-sized CHUNKS
interleaved with decode chunks (a long prompt never stalls the batch),
and when the free list runs dry allocation first EVICTS cache-only
blocks (LRU) and only then PREEMPTS the youngest request — publishes +
releases its blocks, requeues it for recompute-on-readmission — instead
of rejecting.  Concurrency is bounded by memory actually used, not by
``n_slots * T_max`` worst case; docs/SERVING.md has the tuning table.

Four compiled programs cover any request stream:

* **prefill chunk** — ONE ``[1, block_size]`` prompt chunk, cut on the
  device from the prompt as it went up at admission, into the row's
  blocks plus the first-token sample; every prompt length and chunk
  index is the same shape.
* **decode chunk** — up to ``admit_every`` incremental steps for the
  whole batch in one ``lax.while_loop`` (early exit once every row is
  done), with PER-ROW positions through the block tables, keyed only by
  the x2 rung of the gathered block window.
* **verify** (speculative decoding) and **copy-on-write block copy**.

Telemetry rides :mod:`znicz_tpu.observability`: admissions, retirements
(by reason), generated tokens and per-(kind, bucket) compiles are
registry counters; queue depth and active slots are gauges; per-request
latency and time-to-first-token are histograms — all visible on
``/metrics`` and in ``status.json``.  Per-instance views stay available
(``latency`` is a bounded :class:`~znicz_tpu.utils.profiling.LatencyStats`
window feeding the shared latency histogram; ``timer`` is a
:class:`~znicz_tpu.observability.PhaseTimer` whose admit/decode phases
also emit tracer spans — one ``serve/admit`` span per request), and
compile counts are introspectable via
:meth:`PagedDecodeEngine.compile_stats`.

Where the serving thread's time goes is ``loop_clock``'s to say (a
:class:`~znicz_tpu.observability.pipeline.StageClock`; docs/SERVING.md
"Where a turn of the serving thread goes"): every part of a tick is a
stage of it, a lap into ``znicz_serve_loop_seconds{stage}`` and a span
of the same name (``serve/schedule``, ``serve/prefill/{host,wait}``,
``serve/{decode,verify}/{grow,prepare,dispatch,wait,fetch,emit}``,
``serve/verify/draft``), and the stages tile the turn.  ``*/wait`` is
the one place a chunk blocks on the device.  The parents
``serve/admit``, ``serve/prefill``, ``serve/decode`` and
``serve/verify`` keep their extent around ``host``/``wait`` and
``prepare`` to ``fetch``: a decode span holds the prefill chunks queued
ahead of its program, a prefill span the dispatch alone.
``znicz_serve_decode_period_seconds`` and
``znicz_serve_prefill_chunks_between_decodes`` say what stands between
a decode step and the gap a client sees.

A tower that selects the keys it attends reports, with each call's
load sums, what ONE selecting layer did over the call's queries:
``znicz_serve_sparse_keys_scored_total{phase}`` (keys the indexer
scored), ``znicz_serve_sparse_keys_selected_total{phase}`` (keys
attention kept) and ``znicz_serve_sparse_rows_selected_total{phase}``
(rows the selection went over: a prefill chunk's one row; a decode step's
live rows in whole tiles, so over decode steps x slots it is the share of
the batch the selection still visits).

A grouped-query tower with layer kinds reports the cached rows ONE
layer of each kind fetched in a decode chunk and the rows its queries
met: ``znicz_serve_decode_cached_rows_total{kind}`` and
``znicz_serve_decode_attended_rows_total{kind}``.  The first is the
smaller where the live rows of a global layer open with the same blocks
(a prefix the cache holds once), which a tile of 8 rows then reads once
(:func:`~znicz_tpu.ops.pallas.latent_attention.shared_run_decode
_attention`); their ratio is the share of the per-row traffic still paid.

A program call crosses the host-device link once each way: beside a
block table a kind it sends ONE packed int32 array (built fresh for the
call) and what it returns is read in ONE ``jax.device_get``; the rng key
stays on the device and is folded inside the programs.
``znicz_serve_link_crossings_total{program,direction}`` counts both.
"""
from __future__ import annotations

import dataclasses
import hashlib
import time
from collections import OrderedDict, deque
from functools import partial
from typing import Deque, Dict, List, Mapping, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from znicz_tpu import observability
from znicz_tpu.observability import device as device_telemetry, pipeline
from znicz_tpu.services.errors import (
    PrefixCacheUnsupportedError,
    RequestTooLargeError,
    SpeculationUnsupportedError,
)
from znicz_tpu.utils import faults, profiling
from znicz_tpu.workflow.generate import (
    DEFAULT_SPEC_BUCKETS,
    NULL_BLOCK,
    CacheKind,
    PromptLookupDrafter,
    _check_sampling_args,
    _filter_logits,
    _params_fingerprint,
    _sample,
    bucket_for,
    copy_paged_block,
    init_paged_kv,
    paged_decode_step,
    paged_prefill_chunk,
    paged_verify_chunk,
)

# process-wide first-compile ledger backing znicz_serve_compiles_total:
# the jit caches are shared across engines, so a second engine with the
# same (params geometry, program key) compiles NOTHING new and must not
# re-increment the counter.  (jax.clear_caches() invalidates this — the
# counter then under-reports the recompiles; acceptable for a process-
# lifetime first-compile metric.)
_COMPILED_KEYS: set = set()

# seed of the prefix-cache hash chain (versioned: bump if block content
# semantics ever change, so stale-looking hashes can't alias)
_PREFIX_SEED = b"znicz-prefix-v1"


def _chain_digests(tokens: np.ndarray, block_size: int):
    """Chained sha256 over full ``block_size``-token blocks of
    ``tokens``: block j's key commits to ALL tokens before it, so equal
    keys mean equal K/V content, and walking the chain until the first
    miss is the longest-cached-prefix descent of an implicit radix
    structure.  The ONE owner of the keying scheme — the engine's
    prefix cache and the cluster router's affinity index both hash
    through here, so their keys can never drift apart."""
    h = _PREFIX_SEED
    for j in range(tokens.size // block_size):
        h = hashlib.sha256(
            h
            + np.ascontiguousarray(
                tokens[j * block_size:(j + 1) * block_size]
            ).tobytes()
        ).digest()
        yield h


def prefix_block_keys(prompt, block_size: int) -> List[str]:
    """Public prefix-cache block keys for ``prompt`` (hex, full blocks
    only) — the routing key a :class:`~znicz_tpu.cluster.router
    .ServingRouter` indexes replicas by, and what
    :meth:`PagedDecodeEngine.prefix_probe` returns.  Pure function of the
    token content (prompts are hashed as int32, matching the engine's
    internal chain), independent of any live engine state."""
    p = np.asarray(prompt, np.int32).reshape(-1)
    return [h.hex() for h in _chain_digests(p, int(block_size))]


@dataclasses.dataclass
class RequestTimings:
    """Per-request lifecycle breakdown — the answer to "why was this
    request slow", attached to every :class:`Completion` (and the HTTP
    done record).  All host wall-clock (``time.perf_counter`` deltas):

    * ``queue_s`` — time spent WAITING (engine queue before first
      admission, plus every re-queue wait after a preemption; the
      front door adds its own pending-queue wait on top).
    * ``prefill_s`` — wall time of this request's own admit/prefill
      program calls (one per prompt chunk).
    * ``decode_s`` — wall time of the decode chunks this request was
      RESIDENT in.  Chunks are batched, so concurrent residents each
      count the full chunk — a per-request share of shared tower work,
      not a sum that totals to wall time across requests.
    * ``preemptions`` — times this request was evicted and recomputed.
    * ``cached_tokens`` — prompt tokens whose prefill was skipped via
      the prefix cache (accumulated across re-admissions).
    * ``spec_drafted`` / ``spec_accepted`` — draft tokens proposed for
      (and accepted by) this request's speculative verify steps; their
      ratio is the per-request acceptance rate, the number that says
      whether speculation paid for THIS request.
    """

    queue_s: float = 0.0
    prefill_s: float = 0.0
    decode_s: float = 0.0
    preemptions: int = 0
    cached_tokens: int = 0
    spec_drafted: int = 0
    spec_accepted: int = 0

    def as_dict(self) -> Dict:
        return {
            "queue_s": round(self.queue_s, 6),
            "prefill_s": round(self.prefill_s, 6),
            "decode_s": round(self.decode_s, 6),
            "preemptions": self.preemptions,
            "cached_tokens": self.cached_tokens,
            "spec_drafted": self.spec_drafted,
            "spec_accepted": self.spec_accepted,
        }


@dataclasses.dataclass
class Request:
    """One queued generation request: a 1-D prompt with its own budget."""

    id: int
    prompt: np.ndarray  # 1-D int32
    max_new_tokens: int
    bucket: int  # admission width: the prompt padded to whole blocks
    watch: profiling.Stopwatch  # started at submit; read at retirement
    ttft_s: Optional[float] = None  # set once at FIRST admission
    # memoized prefix-cache hash chain (pure function of the prompt —
    # computed once per request; block RESOLUTION stays per-tick fresh)
    digests: Optional[List[bytes]] = None
    # end-to-end tracing: the client-visible id (set by the front door)
    # and the lifecycle breakdown this request accumulates
    trace_id: Optional[str] = None
    timings: RequestTimings = dataclasses.field(
        default_factory=RequestTimings
    )
    # watch-relative instant this request last (re-)entered the queue:
    # 0.0 at submit, bumped at preemption — queue_s accrues from here
    last_queued_at: float = 0.0


@dataclasses.dataclass
class Completion:
    """A finished request: prompt + generated tokens plus its serving
    metrics.  ``latency_s`` is submit -> retirement (queue wait
    included — the number a caller actually experiences); ``ttft_s`` is
    submit -> first sampled token.

    ``finish_reason`` is the full failure taxonomy (docs/SERVING.md):
    ``"eos"`` / ``"budget"`` from the engine itself, plus the typed
    terminations the front door retires with — ``"cancelled"``,
    ``"deadline_exceeded"``, ``"error"`` (engine-thread failure;
    ``error`` carries the message) and ``"shed"`` (dropped at
    shutdown).  ``trace_id`` is the client-visible request id when the
    request came through a :class:`~znicz_tpu.services.frontdoor
    .ServingFrontDoor`."""

    id: int
    tokens: np.ndarray  # prompt + generated, EOS included when hit
    n_new: int
    finish_reason: str  # "eos" | "budget" | typed front-door reasons
    latency_s: float
    tokens_per_sec: float
    bucket: int
    ttft_s: Optional[float] = None
    error: Optional[str] = None  # set for finish_reason == "error"
    trace_id: Optional[str] = None  # front-door request id
    # per-request lifecycle breakdown (RequestTimings.as_dict():
    # queue_s / prefill_s / decode_s / preemptions / cached_tokens)
    timings: Optional[Dict] = None


def _sample_tok(logits, key, temperature, top_p, *, greedy, top_k, nucleus):
    """Engine twin of the generate() sampler: greedy argmax or the
    shared truncated-softmax ``_sample`` (structural knobs static)."""
    if greedy:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return _sample(logits, key, temperature, top_k, nucleus, top_p)


@partial(
    jax.jit,
    static_argnames=(
        "block_size", "n_heads", "greedy", "top_k", "nucleus",
        "moe_top_k", "moe_dispatch", "model",
    ),
    donate_argnums=(1,),
)
def _paged_prefill_prog(
    params, pools, table, prompt, where, temperature, top_p, rng, *,
    block_size, n_heads, greedy, top_k, nucleus, moe_top_k, moe_dispatch,
    model=None,
):
    """One aligned prompt chunk into the row's blocks + first-token
    sample.  ONE compiled shape covers every prompt length and every
    chunk index: ``prompt`` is the whole right-padded prompt, ``[1,
    PagedDecodeEngine.prompt_width]`` on the device since admission, and
    the program cuts its ``[1, block_size]`` chunk at ``offset``;
    ``where`` is ``[offset, last, seq]``, the one int32 array a chunk
    sends beside its table.  ``last`` is the in-chunk index of the
    prompt's final real token (the tail of the final chunk is RIGHT-pad —
    prefix-cache alignment); the sample only matters on the final chunk;
    computing it unconditionally keeps the program single and costs one
    argmax/categorical per chunk.  A sampling structure folds the row's
    ``seq`` into ``rng`` here; greedy never reads a key.

    With a ``model`` (a tower of another kind, e.g.
    :class:`~znicz_tpu.workflow.latent_lm.LatentMoEModel`) the chunk runs
    through ITS tower and the call returns a third value, the chunk's
    expert-load sums."""
    offset, last, seq = where[0], where[1], where[2]
    tokens = jax.lax.dynamic_slice(prompt, (0, offset), (1, block_size))
    if model is None:
        pools, logits = paged_prefill_chunk(
            params, pools, table, tokens, offset, n_heads=n_heads,
            block_size=block_size, last=last, moe_top_k=moe_top_k,
            moe_dispatch=moe_dispatch,
        )
    else:
        pools, logits, load = model.prefill_chunk(
            params, pools, table, tokens, offset, block_size=block_size,
            last=last,
        )
    first = _sample_tok(
        logits, None if greedy else jax.random.fold_in(rng, seq),
        temperature, top_p, greedy=greedy, top_k=top_k, nucleus=nucleus,
    )
    if model is None:
        return pools, first[0]
    return pools, first[0], load


@partial(jax.jit, donate_argnums=(0,))
def _cow_copy_prog(pools, src, dst):
    """Copy-on-write block split (:func:`copy_paged_block` with the
    pools donated): ``src``/``dst`` are traced, so one compiled program
    serves every split of one pool geometry."""
    return copy_paged_block(pools, src, dst)


# rows of the decode chunk's packed operand
_TOK, _POS, _DONE, _REMAINING, _CHUNK = range(5)


@partial(
    jax.jit,
    static_argnames=(
        "chunk", "block_size", "t_max", "n_heads", "eos_id", "greedy",
        "top_k", "nucleus", "moe_top_k", "moe_dispatch", "model",
    ),
    donate_argnums=(1,),
)
def _paged_decode_chunk(
    params, pools, tables, state, temperature, top_p, rng, *, chunk,
    block_size, t_max, n_heads, eos_id, greedy, top_k, nucleus, moe_top_k,
    moe_dispatch, model=None,
):
    """Up to ``chunk`` paged decode steps for the whole batch in ONE
    compiled program, exiting early once every row is done.

    ``state`` is the one int32 ``[5, B]`` array a call sends beside the
    block tables: the rows' ``tok``, ``pos``, ``done`` (a row is done
    where it is not 0) and ``remaining``, then the chunk's index in
    ``[_CHUNK, 0]``.  A sampling structure folds ``1 << 20 | index`` into
    ``rng`` here; greedy never reads a key.

    Per-row positions are native to the paged step (the block table is
    the indirection — no vmap-into-scatter), so no prompt length,
    admission pattern, block assignment or pool occupancy ever
    recompiles this.  Done/idle rows write to the reserved null block
    and their positions FREEZE (a clamped position could walk into a
    table entry the allocator already handed to another row).

    With a ``model`` each step runs through ITS tower (same loop, same
    sampling, same freezing of done rows) and the call returns an eighth
    value: the chunk's expert-load sums, added up step by step on the
    device so that they cost the host nothing but their fetch with the
    chunk's tokens."""
    tok, pos, remaining = state[_TOK], state[_POS], state[_REMAINING]
    done = state[_DONE] != 0
    if not greedy:
        rng = jax.random.fold_in(rng, (1 << 20) | state[_CHUNK, 0])
    b = tok.shape[0]
    # clamp against the FULL positional capacity, never the (possibly
    # narrower) gathered window: the final loop iteration pushes a live
    # row's pos one past this chunk's allocation, and freezing it at
    # the window edge would overwrite the edge slot next step.  The
    # transiently out-of-window pos is harmless — the host re-windows
    # and re-allocates before the next chunk reads it.
    t_cap = t_max - 1
    fill = jnp.int32(eos_id)
    out = jnp.full((b, chunk), fill, jnp.int32)

    def cond(carry):
        i, _, _, _, done, _, _, _ = carry
        return (i < chunk) & ~jnp.all(done)

    def step(pools, tok, pos, done):
        if model is None:
            return paged_decode_step(
                params, pools, tables, tok, pos, n_heads=n_heads,
                block_size=block_size, write_mask=~done,
                moe_top_k=moe_top_k, moe_dispatch=moe_dispatch,
            ) + (None,)
        return model.decode_step(
            params, pools, tables, tok, pos, block_size=block_size,
            write_mask=~done,
        )

    def body(carry):
        i, pools, tok, pos, done, remaining, out, load = carry
        pools, logits, step_load = step(pools, tok, pos, done)
        load = jax.tree_util.tree_map(jnp.add, load, step_load)
        nxt = _sample_tok(
            logits, None if greedy else jax.random.fold_in(rng, i),
            temperature, top_p, greedy=greedy, top_k=top_k, nucleus=nucleus,
        )
        nxt = jnp.where(done, fill, nxt)
        remaining = jnp.where(done, remaining, remaining - 1)
        done = done | (nxt == eos_id) | (remaining <= 0)
        out = jax.lax.dynamic_update_slice(out, nxt[:, None], (0, i))
        pos = jnp.where(done, pos, jnp.minimum(pos + 1, t_cap))
        return (i + 1, pools, nxt, pos, done, remaining, out, load)

    # the load sums' shapes, without running a step: zeros to add onto
    load = jax.tree_util.tree_map(
        lambda leaf: jnp.zeros(leaf.shape, leaf.dtype),
        jax.eval_shape(step, pools, tok, pos, done)[2],
    )
    i, pools, tok, pos, done, remaining, out, load = jax.lax.while_loop(
        cond, body,
        (jnp.int32(0), pools, tok, pos, done, remaining, out, load),
    )
    if model is None:
        return pools, tok, pos, done, remaining, out, i
    return pools, tok, pos, done, remaining, out, i, load


@partial(
    jax.jit,
    static_argnames=(
        "width", "block_size", "n_heads", "greedy", "top_k", "nucleus",
        "moe_top_k", "moe_dispatch",
    ),
    donate_argnums=(1,),
)
def _paged_verify_prog(
    params, pools, tables, batch, temperature, top_p, rng, *, width,
    block_size, n_heads, greedy, top_k, nucleus, moe_top_k, moe_dispatch,
):
    """Speculative VERIFY: score ``width`` input tokens per row — the
    row's current last token plus its drafted continuation — in ONE
    forward pass through the paged attention path
    (:func:`paged_verify_chunk`), then keep each row's longest agreeing
    prefix.

    Returns ``(pools, out [B, width], n_accept [B])``: the host emits
    ``out[b, :n_accept[b] + 1]`` — the accepted drafts plus one BONUS
    token (the verifier's own prediction at the first disagreement, or
    past the last accepted draft) — and advances the row's state by
    that many positions.  Greedy: acceptance is exact argmax agreement
    position by position, so the emitted chain is token-identical to
    non-speculative decode (``out`` IS the greedy prediction at every
    position, conditioned on the drafts before it — valid exactly up to
    and including the bonus slot, which is all the host reads).
    Sampled: standard speculative rejection against the drafter's
    point-mass proposal — draft ``d`` at a position is accepted with
    probability ``p(d)`` under the FILTERED target distribution
    (:func:`~znicz_tpu.workflow.generate._filter_logits` — the same
    truncation :func:`_sample` draws through), a rejection resamples
    from the residual (``p`` with ``d`` masked out), and a position
    with no draft samples ``p`` directly — the emitted marginal is the
    target distribution exactly (Leviathan et al. 2023).

    ``batch`` is the one int32 ``[B, width + 5]`` array a call sends
    beside the block tables: a row's ``width`` input tokens, then its
    ``pos``, ``done`` (not 0), ``n_write`` and ``draft_len``, then the
    chunk's index (row 0 is read; a sampling structure folds ``1 << 20 |
    index`` into ``rng`` here).  ``width`` is the bucketed verify shape;
    ``draft_len``/``n_write`` are TRACED, so rows with shorter drafts,
    smaller budgets, or no draft at all (emit 1 token — a plain decode
    step's worth) ride the same compiled program: zero new programs per
    accepted length."""
    tokens, pos, done = batch[:, :width], batch[:, width], batch[:, width + 1]
    done = done != 0
    n_write, draft_len = batch[:, width + 2], batch[:, width + 3]
    if not greedy:
        rng = jax.random.fold_in(rng, (1 << 20) | batch[0, width + 4])
    b = tokens.shape[0]
    idx = jnp.arange(width)[None, :]
    wmask = (~done)[:, None] & (idx < n_write[:, None])
    pools, logits = paged_verify_chunk(
        params, pools, tables, tokens, pos, n_heads=n_heads,
        block_size=block_size, write_mask=wmask, moe_top_k=moe_top_k,
        moe_dispatch=moe_dispatch,
    )
    # position i predicts the token AFTER input token i; the draft for
    # it is tokens[:, i+1], which exists iff i < draft_len
    has_draft = idx < draft_len[:, None]
    d_next = jnp.concatenate(
        [tokens[:, 1:], jnp.zeros((b, 1), jnp.int32)], axis=1
    )
    if greedy:
        out = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        acc = (out == d_next) & has_draft
    else:
        flt = _filter_logits(logits, temperature, top_k, nucleus, top_p)
        probs = jax.nn.softmax(flt, axis=-1)
        p_draft = jnp.take_along_axis(probs, d_next[..., None], axis=-1)[
            ..., 0
        ]
        u = jax.random.uniform(jax.random.fold_in(rng, 0), p_draft.shape)
        acc = (u <= p_draft) & has_draft
        # correction at a drafted position resamples the RESIDUAL (the
        # rejected draft masked out); an undrafted position samples the
        # filtered distribution directly (the plain-decode draw)
        vocab = flt.shape[-1]
        is_d = (
            jnp.arange(vocab)[None, None, :] == d_next[..., None]
        ) & has_draft[..., None]
        corr = jax.random.categorical(
            jax.random.fold_in(rng, 1),
            jnp.where(is_d, -jnp.inf, flt),
            axis=-1,
        ).astype(jnp.int32)
        out = jnp.where(acc, d_next, corr)
    n_accept = jnp.sum(
        jnp.cumprod(acc.astype(jnp.int32), axis=1), axis=1
    )
    return pools, out, n_accept


class _BlockKind:
    """The block allocator's state for ONE kind of cached state
    (:class:`~znicz_tpu.workflow.generate.CacheKind`): which pool blocks
    are free, how many tables reference each, the prefix cache's maps, and
    a block table a row.  The engine holds one of these for each kind its
    tower declares (a tower that declares none has one, every layer's) and
    runs the same allocation, release and preemption through each.

    A row's blocks cover a contiguous run of ABSOLUTE block indices
    ``[row_base, row_base + len(row_blocks))``; the block of index ``b``
    sits at table entry ``b % width``.  A kind that keeps every token has
    ``row_base`` 0 and a table as wide as the longest row, so the entry IS
    the index.  A WINDOW kind gives back the blocks that lie wholly behind
    the last ``window`` keys of the row's next query, ``row_base`` moves
    up and the table is a ring just wide enough that no two live blocks
    meet in an entry: the window's blocks, the block being written, and
    the blocks one decode chunk can add before the host looks again."""

    def __init__(self, kind: CacheKind, *, n_blocks: Optional[int],
                 batch_size: int, row_blocks: int, block_size: int,
                 chunk: int):
        self.name, self.window = kind.name, kind.window
        self.block_size = block_size
        self.width = row_blocks if kind.window is None else min(
            row_blocks, -(-(kind.window + chunk - 2) // block_size) + 1
        )
        # default: every slot could hold its widest table (plus the
        # reserved null block); shrink it to save memory
        self.n_blocks = int(
            n_blocks if n_blocks is not None else batch_size * self.width + 1
        )
        # LIFO free list: a just-freed (still cache/HBM-warm) block is
        # the next one handed out; block 0 stays reserved as null
        self.free: List[int] = list(range(1, self.n_blocks))
        # per-block refcount = how many tables reference it; the cache
        # reference is tracked separately by block_hash membership
        self.ref = np.zeros(self.n_blocks, np.int64)
        # prefix cache: chained content hash -> block, its inverse, and
        # an LRU over CACHE-ONLY blocks (refcount 0: evictable)
        self.cache: Dict[bytes, int] = {}
        self.block_hash: Dict[int, bytes] = {}
        self.lru: OrderedDict = OrderedDict()
        self.row_blocks: List[List[int]] = [[] for _ in range(batch_size)]
        self.row_base = [0] * batch_size
        self.tables = np.full((batch_size, self.width), NULL_BLOCK, np.int32)
        self.block_bytes = 0  # a block's footprint over this kind's layers
        self.n_released = 0

    @property
    def usable(self) -> int:
        """Capacity available to requests (null block excluded)."""
        return self.n_blocks - 1

    @property
    def allocatable(self) -> int:
        return len(self.free) + len(self.lru)

    @property
    def referenced(self) -> int:
        return self.usable - self.allocatable

    def first_needed(self, pos: int) -> int:
        """The oldest block index a query at ``pos`` still reads."""
        if self.window is None:
            return 0
        return max(pos - self.window + 1, 0) // self.block_size

    def most_held(self, n_blocks: int) -> int:
        """The most blocks a row of ``n_blocks`` blocks of tokens ever
        holds at once."""
        return min(n_blocks, self.width)

    def incref(self, blk: int) -> None:
        self.ref[blk] += 1

    def decref(self, blk: int) -> None:
        """Drop one table reference; at zero the block becomes
        EVICTABLE cache (if published) or returns to the free list."""
        self.ref[blk] -= 1
        if self.ref[blk] == 0:
            if blk in self.block_hash:
                # fresh insertion lands at the MRU end (a block enters
                # the LRU only here, and claiming removed it first)
                self.lru[blk] = None
            else:
                self.free.append(blk)

    def shared(self, blk: int) -> bool:
        """A block a row must NOT write into: other tables still
        reference it, or the prefix cache does (a write would corrupt
        content a future lookup trusts)."""
        return self.ref[blk] > 1 or blk in self.block_hash

    def forget(self, h: bytes) -> None:
        """Drop the cache's entry for chain hash ``h``, if it has one; a
        block nothing else references returns to the free list."""
        blk = self.cache.pop(h, None)
        if blk is None:
            return
        del self.block_hash[blk]
        if blk in self.lru:
            del self.lru[blk]
            self.free.append(blk)

    def push(self, slot: int, blk: int) -> None:
        """``blk`` (referenced by the caller) covers the row's next
        block index."""
        row = self.row_blocks[slot]
        self.tables[slot, (self.row_base[slot] + len(row)) % self.width] = blk
        row.append(blk)

    def drop_behind(self, slot: int, first: int) -> int:
        """Give back the row's blocks below block index ``first``;
        returns how many."""
        row, n = self.row_blocks[slot], 0
        while row and self.row_base[slot] < first:
            self.decref(row.pop(0))
            self.tables[slot, self.row_base[slot] % self.width] = NULL_BLOCK
            self.row_base[slot] += 1
            n += 1
        self.n_released += n
        return n

    def drop_past(self, slot: int, keep: int) -> None:
        """Give back the row's blocks from block index ``keep`` on."""
        row, base = self.row_blocks[slot], self.row_base[slot]
        while row and base + len(row) > keep:
            self.decref(row.pop())
            self.tables[slot, (base + len(row)) % self.width] = NULL_BLOCK

    def device_table(self, view: np.ndarray) -> jax.Array:
        """``view`` of :attr:`tables` for a program.  A ring's entries are
        rewritten while the row lives, and prefill chunks are dispatched
        without waiting for the one before: on a backend that reads host
        memory in place (the CPU does) the program must get a copy, or
        the next chunk's slide races the chunk in flight.  A plain table's
        written entries never change under a running program."""
        return jnp.asarray(view if self.window is None else view.copy())

    def release_row(self, slot: int) -> None:
        """Drop every table reference of ``slot`` (reverse order keeps
        the free list LIFO — last-allocated, still-warm block first)."""
        self.drop_past(slot, 0)
        self.row_base[slot] = 0
        self.tables[slot, :] = NULL_BLOCK


class PagedDecodeEngine:
    """Continuous micro-batching over a paged K/V cache: refcounted
    copy-on-write block pool, cross-request prefix cache, chunked
    prefill, preemption under pressure (docs/SERVING.md "Paged KV
    serving").

    Usage::

        eng = PagedDecodeEngine(params, n_heads=8, eos_id=0, batch_size=8)
        ids = [eng.submit(prompt, max_new_tokens=64) for prompt in reqs]
        completions = eng.run()          # drain the queue
        eng.stats()                      # latency / tokens/s / compiles

    Greedy by default; ``temperature``/``top_k``/``top_p`` select the
    same sampling structures as :func:`~znicz_tpu.workflow.generate
    .generate` (one compiled program set per structure).
    ``admit_every`` is the admission granularity: the batch decodes in
    chunks of that many steps between retirement checks — small values
    admit sooner, large values sync less.

    K/V live in a shared ``[n_blocks, block_size, H*hd]`` pool per
    layer; each slot owns an
    ordered block table and every pool block carries a REFCOUNT — the
    same physical block can appear in many tables at once.  (A tower
    that declares kinds of cached state, ``model.cache_kinds``, gets
    pools, free list, refcounts and a table a row for EACH kind, through
    the same allocator; ``n_blocks`` is then ``{kind: blocks}``, and a
    cached chain has a holder a kind, a window kind's for the match's last
    window only; with a window kind the prefix cache is off unless asked
    for by name: docs/SERVING.md "Block tables by layer kind".)  Four
    properties follow:

    * **memory-proportional concurrency** — a slot consumes blocks for
      the tokens it has actually decoded, not a ``T_max`` reservation;
      ``n_blocks`` (not ``batch_size * T_max``) is the real capacity,
      so short requests pack many-deep into the same memory.
    * **prefix reuse (RadixAttention/vLLM lineage)** — retiring (and
      preempted) requests publish their COMPLETED full blocks into a
      prefix cache keyed by CHAINED content hash (block j's key commits
      to all tokens before it — an implicit radix structure); admission
      maps the longest cached block-chain prefix of the prompt into the
      new table with refcount bumps and chunk-prefills only the
      uncached tail.  A fully-cached system prompt costs zero prefill
      FLOPs (one chunk reruns for the first-token logits) and TTFT
      collapses to the tail.  Shared blocks are READ-ONLY: a write into
      a block other tables or the cache reference COW-splits it first.
      Prompts anchor at position 0 and right-pad the final chunk so a
      shared prefix fills identical block contents whatever the full
      prompt's length.
    * **chunked prefill** — prompts are processed in block-sized chunks
      under a per-tick TOKEN budget (``prefill_budget``,
      Sarathi-style), interleaved with decode chunks: admitting a long
      prompt steals a bounded slice of tower work between decode chunks
      instead of stalling rows mid-decode.
    * **eviction before preemption** — when the free list is dry,
      allocation first EVICTS the least-recently-used cache-only block
      (refcount 0, cache-referenced); only when the cache too is empty
      is the YOUNGEST occupant preempted: publishes its full blocks to
      the cache, releases its references, requeues at the queue head
      for recompute on readmission (often straight out of its own
      just-cached blocks).  Refcounts keep survivors' shared blocks
      alive through any preemption.  If the starved slot is itself the
      youngest it requeues itself and waits for older rows to retire;
      submit-time validation guarantees any single request fits an
      empty pool, so the wait always terminates.

    ONE prefill program plus a short x2 ladder of decode-chunk
    variants cover any stream: the ``[1, block_size]`` prefill chunk
    serves every prompt length, and the decode chunk is keyed only by
    the active block-WINDOW rung (the gather spans the blocks active
    rows actually hold, rounded up a power of two — so short requests
    don't pay ``T_max``-wide attention and the variant count stays
    logarithmic); block tables, chunk offsets, pool occupancy,
    admission patterns AND prefix-cache hits are all traced operands —
    prefix reuse adds ZERO compiled programs, it only skips iterations
    of the existing chunk program.

    ``block_size`` trades utilization against program width;
    ``n_blocks`` defaults to a full ``T_max`` window a slot
    (``batch_size * ceil(T_max/block_size) + 1``) — size it DOWN to
    serve the same stream in less memory, or raise ``batch_size``
    against the same pool to convert reclaimed padding into
    concurrency.  ``prefix_cache=False`` disables sharing (blocks then
    free directly at release, LIFO)."""

    def __init__(
        self,
        params,
        *,
        n_heads: int,
        eos_id: int,
        batch_size: int = 8,
        max_seq: Optional[int] = None,
        block_size: int = 16,
        n_blocks: Union[int, Mapping[str, int], None] = None,
        prefill_budget: Optional[int] = None,
        prefix_cache: Optional[bool] = None,
        admit_every: int = 8,
        pad_id: Optional[int] = None,
        temperature: float = 0.0,
        top_k: int = 0,
        top_p: float = 1.0,
        rng: Optional[jax.Array] = None,
        moe_top_k: int = 1,
        moe_dispatch: str = "dense",
        spec_k: int = 0,
        drafter=None,
        spec_buckets: Sequence[int] = DEFAULT_SPEC_BUCKETS,
        model=None,
    ):
        if batch_size < 1 or admit_every < 1:
            raise ValueError(
                f"want batch_size >= 1 and admit_every >= 1; got "
                f"{batch_size}, {admit_every}"
            )
        if block_size < 1:
            raise ValueError(f"want block_size >= 1; got {block_size}")
        self.block_size = int(block_size)
        self._m_unsupported = observability.counter(
            "znicz_serve_unsupported_total",
            "engine configurations refused because the tower does not "
            "serve the feature yet",
            ("feature",),
        )
        # the kinds of cached state the tower keeps: one (every layer's)
        # unless it declares its own (workflow/generate.CacheKind)
        kinds = getattr(model, "cache_kinds", None)
        # ON by default where a row's blocks stay its own for its whole
        # life (the one-kind tower, and a tower that declares ONE kind with
        # no window, whatever arrays a block of it holds): sharing is free
        # when nothing matches (a few sha256 per admission) and the
        # headline win when it does.  A tower with a WINDOW kind gives that
        # kind's blocks back while the row lives, so a chain has a holder A
        # KIND (global blocks for the whole match, window blocks for its
        # last window) and is handed to a new request only where every kind
        # still holds its part: served when asked for by name, OFF by
        # default (such a tower then hashes nothing and keeps no cache-only
        # block)
        gives_back = any(k.window is not None for k in kinds or ())
        self.prefix_cache = (
            not gives_back and len(kinds or ()) <= 1
            if prefix_cache is None else bool(prefix_cache)
        )
        # speculative decoding (docs/SERVING.md "Speculative decoding"):
        # spec_k == 0 is OFF (the plain decode chunk runs); > 0 drafts
        # up to spec_k tokens per decoding row each tick and verifies
        # them in one bucketed forward pass.  The drafter is duck-typed
        # (``propose(context, k)``) — prompt-lookup by default, a
        # draft-model drafter plugs into the same hook.
        if spec_k < 0:
            raise ValueError(f"want spec_k >= 0; got {spec_k}")
        if spec_k and model is not None:
            self._m_unsupported.labels(feature="speculation").inc()
            raise SpeculationUnsupportedError(
                f"a {type(model).__name__} tower has no verify program "
                "yet: speculative decoding is served for the classic "
                "tower only"
            )
        self.spec_k = int(spec_k)
        self.spec_buckets = tuple(int(w) for w in spec_buckets)
        if (
            not self.spec_buckets
            or min(self.spec_buckets) < 2
            or list(self.spec_buckets)
            != sorted(set(self.spec_buckets))
        ):
            raise ValueError(
                "spec_buckets must be strictly increasing verify "
                f"widths >= 2 (k+1 rungs); got {spec_buckets}"
            )
        if drafter is not None and not self.spec_k:
            # silently serving with speculation OFF would be a config
            # trap
            raise ValueError(
                "a drafter was given but spec_k == 0 keeps speculation "
                "off; pass spec_k >= 1 to enable it"
            )
        self.drafter = (
            drafter if drafter is not None else PromptLookupDrafter()
        ) if self.spec_k else None
        # per-tick prefill token budget: how much admission work may
        # ride between two decode chunks.  The default matches one
        # decode chunk's per-row depth (admit_every steps) in tokens —
        # admission and decode then make comparable progress per tick
        self.prefill_budget = int(
            prefill_budget if prefill_budget is not None
            else max(admit_every, 1) * self.block_size
        )
        if self.prefill_budget < 1:
            raise ValueError(
                f"want prefill_budget >= 1; got {self.prefill_budget}"
            )
        # the tower's KIND: None is the classic block of
        # workflow/transformer.py (learned positions, a k/v pair a
        # layer); anything else brings its own paged tower
        # (``init_pools`` / ``prefill_chunk`` / ``decode_step``, e.g.
        # workflow/latent_lm.LatentMoEModel) and its own context limit
        self.model = model
        max_pos = (
            params[0]["pos"].shape[0] if model is None
            else int(model.max_positions)
        )
        self.t_max = int(max_seq or max_pos)
        if self.t_max > max_pos:
            raise ValueError(
                f"max_seq {self.t_max} exceeds the positional table "
                f"({max_pos})"
            )
        top_k, rng = _check_sampling_args(
            params, temperature, top_k, top_p, rng, eos_id
        )
        self.params = params
        self._params_fp = _params_fingerprint(params)
        self.n_heads = n_heads
        self.eos_id = int(eos_id)
        self.pad_id = int(pad_id if pad_id is not None else eos_id)
        self.batch_size = int(batch_size)
        self.admit_every = int(admit_every)
        self.moe_top_k = moe_top_k
        self.moe_dispatch = moe_dispatch
        self._temperature = jnp.float32(temperature)
        self._top_p = jnp.float32(top_p)
        self._rng = rng
        # static sampling structure: one compiled program set per value
        self._structure = (temperature == 0.0, top_k, top_p < 1.0)
        # the rows' state is ONE array, so that a decode chunk sends it in
        # one piece (a copy: :func:`_paged_decode_chunk`'s ``state``);
        # the names are views of its rows
        self._state = np.zeros((5, self.batch_size), np.int32)
        self._tok, self._pos, self._done, self._remaining = self._state[:4]
        self._done[:] = 1  # empty slots idle as done
        self._slots: List[Optional[dict]] = [None] * self.batch_size
        self._queue: Deque[Request] = deque()
        self._order: List[Completion] = []
        self.completions: Dict[int, Completion] = {}
        # process-wide registry series (shared across engines: get-or-
        # create); per-instance windows ride LatencyStats / PhaseTimer
        self._m_submitted = observability.counter(
            "znicz_serve_requests_submitted_total",
            "requests accepted into the engine queue",
        )
        self._m_admitted = observability.counter(
            "znicz_serve_requests_admitted_total",
            "requests prefilled into a batch slot",
        )
        self._m_retired = observability.counter(
            "znicz_serve_requests_retired_total",
            "completed requests by finish reason",
            ("reason",),
        )
        self._m_tokens = observability.counter(
            "znicz_serve_tokens_generated_total",
            "generated tokens across all retired requests",
        )
        self._m_compiles = observability.counter(
            "znicz_serve_compiles_total",
            "distinct compiled engine programs by kind and bucket",
            ("kind", "bucket"),
        )
        self._m_program_hits = observability.counter(
            "znicz_serve_program_hits_total",
            "program invocations served from an already-compiled entry",
        )
        self._m_queue_depth = observability.gauge(
            "znicz_serve_queue_depth", "requests waiting for a slot"
        )
        self._m_active = observability.gauge(
            "znicz_serve_active_slots", "batch slots decoding right now"
        )
        self._m_latency = observability.histogram(
            "znicz_serve_request_latency_seconds",
            "submit -> retirement latency per request (queue wait included)",
        )
        self._m_ttft = observability.histogram(
            "znicz_serve_ttft_seconds",
            "submit -> first sampled token per request",
        )
        self._m_queue_wait = observability.histogram(
            "znicz_serve_engine_queue_wait_seconds",
            "seconds a request waited in the engine's own queue before "
            "a slot took it (once per admission; a preempted request "
            "is observed again when it is re-admitted)",
        )
        # what a client sees between two batches of tokens: the period
        # from the end of one decode (or verify) chunk's wait to the end
        # of the next one's, and the prefill chunks dispatched in it;
        # observed only while rows decode at every turn in between
        self._m_decode_period = observability.histogram(
            "znicz_serve_decode_period_seconds",
            "seconds from the end of one decode or verify chunk's wait "
            "for the device to the end of the next one's, rows decoding "
            "throughout (over the steps a chunk: the engine's own time "
            "per output token)",
        )
        self._m_prefill_between = observability.histogram(
            "znicz_serve_prefill_chunks_between_decodes",
            "prefill chunks dispatched since the decode or verify chunk "
            "before (observed beside znicz_serve_decode_period_seconds)",
            buckets=(0.0, 1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0),
        )
        self._period_start: Optional[float] = None
        self._prefill_since_decode = 0
        # a program call should cross the host-device link once each way,
        # beside a table a kind: what says whether it does
        crossings = observability.counter(
            "znicz_serve_link_crossings_total",
            "host-device crossings the engine makes for its program "
            "calls: up, the buffers it creates on the device (a prompt's "
            "tokens, once, at admission among them); down, its blocking "
            "reads (one read of a whole tuple is one)",
            ("program", "direction"),
        )
        self._m_crossings = {
            (program, direction): crossings.labels(
                program=program, direction=direction
            )
            for program in ("prefill", "decode", "verify")
            for direction in ("up", "down")
        }
        # the stages that tile one turn of the serving thread; a front
        # door puts its own clock here, so that its stages and the
        # engine's are one iteration
        self.loop_clock = pipeline.serving_loop_clock()
        self.latency = profiling.LatencyStats(
            observe=self._m_latency.observe
        )
        self.timer = observability.PhaseTimer(
            "znicz_serve_phase_seconds",
            help="engine admit/decode host phase seconds",
            span_prefix="serve/",
        )
        # fleet tracing: the serving instance this engine's spans
        # belong to (set by the front door; rides every span/instant
        # as an ``instance`` arg so the trace collector's merged view
        # can split an in-process fleet into per-instance tracks)
        self.trace_instance: Optional[str] = None
        self._programs: Dict[tuple, int] = {}
        self._program_hits = 0
        self._next_id = 0
        self._n_admits = 0
        self._chunk_idx = 0
        self._total_new = 0
        self._peak_active = 0
        m = -(-self.t_max // self.block_size)  # a full row in blocks: ceil
        self.blocks_per_row = m
        # every prompt goes up padded to this one width, so that one
        # prefill program serves them all
        self.prompt_width = m * self.block_size
        # one allocator state a kind; ``n_blocks`` is a number for the
        # one-kind tower and {kind: blocks} where the tower declares kinds
        # (a kind left out gets a full table a slot)
        if kinds and isinstance(n_blocks, int):
            raise ValueError(
                f"a {type(model).__name__} tower keeps blocks of kinds "
                f"{[k.name for k in kinds]}: give n_blocks by kind"
            )
        self._kinds: List[_BlockKind] = [
            _BlockKind(
                kind,
                n_blocks=(n_blocks or {}).get(kind.name) if kinds else n_blocks,
                batch_size=self.batch_size, row_blocks=m,
                block_size=self.block_size, chunk=self.admit_every,
            )
            for kind in (kinds or (CacheKind("kv"),))
        ]
        self._by_kind = bool(kinds)
        self.n_blocks = (
            {k.name: k.n_blocks for k in self._kinds} if kinds
            else self._kinds[0].n_blocks
        )
        self._pools = (
            init_paged_kv if self.model is None else self.model.init_pools
        )(self.params, self.n_blocks, self.block_size)
        self._n_prefix_hits = 0
        self._n_prefix_misses = 0
        self._n_cached_tokens = 0
        self._n_evictions = 0
        self._n_cow = 0
        # one admission EVENT per request, ever: a preempted request's
        # readmission must not re-fire the serve/admit span, the
        # admitted counter, or the TTFT histogram (its first token was
        # already produced once — re-observing would double-count)
        self._admitted_ids: set = set()
        self._n_preempted = 0
        # per-block footprint across a kind's layers (a k/v pair a
        # layer, or one array of cached rows) — the byte twin of the
        # block gauges, so pool pressure is readable in the same unit
        # device memory is
        layer_kinds = (
            self.model.layer_kinds if kinds
            else [self._kinds[0].name] * len(self._pools)
        )
        for kind in self._kinds:
            kind.block_bytes = sum(
                int(np.prod(leaf.shape[1:])) * np.dtype(leaf.dtype).itemsize
                for pool, name in zip(self._pools, layer_kinds)
                if name == kind.name
                for leaf in jax.tree_util.tree_leaves(pool)
            )
        # what block_size tokens cost across the whole tower while every
        # kind holds them
        self.block_bytes = sum(k.block_bytes for k in self._kinds)
        self._m_pool = observability.gauge(
            "znicz_serve_kv_pool_blocks",
            "paged KV pool blocks by state (the null block is excluded)",
            ("state",),
        )
        self._m_pool_bytes = observability.gauge(
            "znicz_serve_kv_pool_bytes",
            "paged KV pool bytes by state (blocks x per-block K/V "
            "bytes across the tower; the null block is excluded)",
            ("state",),
        )
        self._m_in_use = observability.gauge(
            "znicz_serve_pool_blocks_in_use",
            "pool blocks that a row's table references, by kind of block",
            ("kind",),
        )
        self._m_window_released = observability.counter(
            "znicz_serve_window_blocks_released_total",
            "blocks a window kind gave back while their row lived on, "
            "because they lay wholly behind the row's window",
        )
        self._m_bytes_per_token = observability.histogram(
            "znicz_serve_cache_bytes_per_resident_token",
            "per tick: bytes of the pool blocks that rows' tables "
            "reference (every kind) over the tokens resident in those "
            "rows; sum / count is the mean over ticks",
            buckets=(512.0, 2048.0, 8192.0, 32768.0, 131072.0, 524288.0),
        )
        self._m_preempted = observability.counter(
            "znicz_serve_preemptions_total",
            "requests preempted under pool pressure (freed + requeued)",
        )
        self._m_prefill_chunks = observability.counter(
            "znicz_serve_prefill_chunks_total",
            "prompt chunks run by the paged prefill program",
        )
        self._m_prefix_hits = observability.counter(
            "znicz_serve_prefix_hits_total",
            "prompt blocks mapped from the prefix cache at admission",
        )
        self._m_prefix_misses = observability.counter(
            "znicz_serve_prefix_misses_total",
            "full prompt blocks that missed the prefix cache at admission",
        )
        self._m_prefix_tokens = observability.counter(
            "znicz_serve_prefix_cached_tokens_total",
            "prompt tokens whose prefill was skipped via the prefix cache",
        )
        self._m_prompt_tokens = observability.counter(
            "znicz_serve_prompt_tokens_total",
            "prompt tokens of the requests bound to a slot (a readmission "
            "after a preemption counts again, as its cached tokens do): "
            "what znicz_serve_prefix_cached_tokens_total is a share of",
        )
        self._m_prefix_evictions = observability.counter(
            "znicz_serve_prefix_evictions_total",
            "cached blocks evicted to satisfy allocation pressure",
        )
        self._m_prefix_mapped = observability.counter(
            "znicz_serve_prefix_blocks_mapped_total",
            "blocks a request bound to a slot took from the prefix cache, "
            "by kind of cache block (a window kind's are the match's last "
            "window)",
            ("kind",),
        )
        self._m_prefix_hit_requests = observability.counter(
            "znicz_serve_prefix_hit_requests_total",
            "requests bound to a slot that mapped a cached chain (what "
            "znicz_serve_prefix_blocks_mapped_total is a mean over)",
        )
        self._m_prefix_cut = observability.counter(
            "znicz_serve_prefix_chain_cut_total",
            "admissions whose cached chain was shortened or lost",
            ("reason",),
        )
        # speculative decoding tallies (zero and silent while spec is
        # off; the registry series are process-wide get-or-create)
        self._n_spec_drafted = 0
        self._n_spec_accepted = 0
        self._n_spec_rejected = 0
        self._n_verify_steps = 0
        self._m_spec_drafted = observability.counter(
            "znicz_serve_spec_drafted_total",
            "draft tokens proposed to the speculative verifier",
        )
        self._m_spec_accepted = observability.counter(
            "znicz_serve_spec_accepted_total",
            "draft tokens the speculative verifier accepted",
        )
        self._m_spec_rejected = observability.counter(
            "znicz_serve_spec_rejected_total",
            "draft tokens rejected and rolled back (table truncate)",
        )
        self._m_spec_accept_len = observability.histogram(
            "znicz_serve_spec_accept_length",
            "accepted draft tokens per row per verify step",
            buckets=(0.0, 1.0, 2.0, 3.0, 4.0, 6.0, 8.0, 12.0, 16.0),
        )
        # what the decode and verify programs read of the cache, which
        # is what their device time scales with: for the K/V tower every
        # slot's window of positions per step, active or not; for a tower
        # that reads its pool in place, the live rows' whole blocks
        self._m_decode_steps = observability.counter(
            "znicz_serve_decode_steps_total",
            "token steps run by the paged decode and verify programs "
            "(a verify chunk is one step)",
        )
        self._m_decode_gathered = observability.counter(
            "znicz_serve_decode_gathered_tokens_total",
            "cached positions a layer of the paged decode and verify "
            "programs read: steps x slots x window blocks x block size "
            "where the window is gathered; the decoding rows' lengths in "
            "whole blocks, step by step, where the pool is read in place",
        )
        self._m_decode_cached_rows = observability.counter(
            "znicz_serve_decode_cached_rows_total",
            "cached rows ONE layer of a kind read in the paged decode "
            "program, summed over its steps, for a tower that declares "
            "kinds (times the kind's layers: what the tower read)",
            ("kind",),
        )
        self._m_decode_attended_rows = observability.counter(
            "znicz_serve_decode_attended_rows_total",
            "cached rows the queries of ONE layer of a kind met in the "
            "paged decode program, a row counted for each query that met "
            "it, summed over its steps; cached_rows_total over this is "
            "the share of that traffic still fetched (under 1 where "
            "several rows read the blocks they share once)",
            ("kind",),
        )
        self._m_decode_chunks = observability.counter(
            "znicz_serve_decode_chunks_total",
            "paged decode and verify chunks by gather window (blocks)",
            ("window",),
        )
        # routed experts held here (a tower that reports its expert
        # load; silent for every other): the sums come back with each
        # call's outputs, so reading them adds no sync
        self._load_backlog: List[dict] = []
        self._routed_layers = (
            self.model.routed_layers(self.params) if self.model else 0
        )
        self._m_moe_pairs = observability.counter(
            "znicz_serve_moe_pairs_total",
            "(token, choice) pairs computed by the experts held here, "
            "by held expert and by the phase that routed them",
            ("phase", "expert"),
        )
        self._m_moe_busiest = observability.counter(
            "znicz_serve_moe_busiest_pairs_total",
            "pairs of the busiest held expert, summed over routed "
            "layers and steps (over znicz_serve_moe_layer_steps_total: "
            "the mean busiest load)",
            ("phase",),
        )
        self._m_moe_idle = observability.counter(
            "znicz_serve_moe_idle_experts_total",
            "held experts that received no pair, summed over routed "
            "layers and steps",
            ("phase",),
        )
        self._m_moe_layer_steps = observability.counter(
            "znicz_serve_moe_layer_steps_total",
            "routed layers x token steps (decode) or chunks (prefill) "
            "whose expert load was counted",
            ("phase",),
        )
        # a tower that selects the keys it attends (silent for every
        # other): sums that come back with each call's load
        self._m_sparse_scored = observability.counter(
            "znicz_serve_sparse_keys_scored_total",
            "cached keys the indexer of ONE selecting layer scored, summed "
            "over the queries of the phase's calls",
            ("phase",),
        )
        self._m_sparse_selected = observability.counter(
            "znicz_serve_sparse_keys_selected_total",
            "cached keys ONE selecting layer's attention read after the "
            "selection, summed over the queries of the phase's calls",
            ("phase",),
        )
        self._m_sparse_rows = observability.counter(
            "znicz_serve_sparse_rows_selected_total",
            "rows ONE selecting layer's selection went over, summed over "
            "the phase's calls: a prefill chunk its one row; a decode step "
            "its live rows in whole tiles (over decode steps x slots: the "
            "share of the batch the selection still visits)",
            ("phase",),
        )
        self._update_pool_gauges()

    def _upload(self, program: str, host: np.ndarray) -> jax.Array:
        """One buffer on the device for a call of ``program``.  ``host``
        is not written after this: a backend that reads host memory in
        place (the CPU does) may still be reading it when the call
        returns."""
        self._m_crossings[program, "up"].inc()
        return jnp.asarray(host)

    def _read(self, program: str, outputs):
        """ONE blocking read for a call of ``program``: its ``outputs``
        and, with them, the load sums of the prefill chunks dispatched
        since the last read (they ran ahead of this call on the device;
        reading them any sooner would wait for their chunk).  Every
        leaf's copy starts before the first is waited for.  Returns
        (outputs, those load sums), all on the host."""
        backlog, self._load_backlog = self._load_backlog, []
        self._m_crossings[program, "down"].inc()
        return jax.device_get((outputs, backlog))

    def _count_loads(self, phase: str, loads, calls: int) -> None:
        """Fold the load sums of some calls, FETCHED already
        (:meth:`_read`), into the registry; each covers ``calls`` token
        steps or chunks."""
        if not calls:
            return
        for load in loads:
            if not load:
                continue
            if "sparse_scored" in load:
                self._m_sparse_scored.labels(phase=phase).inc(
                    int(load["sparse_scored"])
                )
                self._m_sparse_selected.labels(phase=phase).inc(
                    int(load["sparse_selected"])
                )
                self._m_sparse_rows.labels(phase=phase).inc(
                    int(load["sparse_rows"])
                )
            if "pairs" not in load:
                continue
            for expert, n in enumerate(load["pairs"]):
                self._m_moe_pairs.labels(phase=phase, expert=expert).inc(
                    int(n)
                )
            self._m_moe_busiest.labels(phase=phase).inc(int(load["busiest"]))
            self._m_moe_idle.labels(phase=phase).inc(int(load["idle"]))
            self._m_moe_layer_steps.labels(phase=phase).inc(
                calls * self._routed_layers
            )

    # -- request intake ---------------------------------------------------

    def _validate_request(self, p: np.ndarray, max_new_tokens: int) -> int:
        """Check the request against the real KV capacity (the
        positional window, then the block pool — the error names which
        ran out); returns the admission width, the prompt padded to
        whole blocks."""
        padded = -(-p.size // self.block_size) * self.block_size
        total = padded + max_new_tokens
        need = -(-total // self.block_size)
        if total > self.t_max:
            raise RequestTooLargeError(
                f"prompt (len {p.size}, padded {padded}) + max_new_tokens "
                f"{max_new_tokens} exceeds the positional "
                f"window (t_max={self.t_max})"
            )
        for kind in self._kinds:
            held = kind.most_held(need)
            if held > kind.usable:
                of = f" of kind {kind.name!r}" if self._by_kind else ""
                raise RequestTooLargeError(
                    f"prompt (len {p.size}, padded {padded}) + "
                    f"max_new_tokens {max_new_tokens} needs {held} KV "
                    f"blocks{of}; exceeds the paged KV pool "
                    f"({kind.usable} usable blocks x {self.block_size} "
                    "tokens)"
                )
        return padded  # admission width: the padded prompt length

    def submit(
        self,
        prompt,
        max_new_tokens: int,
        *,
        trace_id: Optional[str] = None,
    ) -> int:
        """Queue one prompt (1-D token ids); returns the request id.
        Validated against the real KV capacity, so
        admission can never fail later.  ``trace_id`` (the front door's
        client-visible id) rides into the request's lifecycle spans and
        its completion."""
        p = np.asarray(prompt, np.int32).reshape(-1)
        if p.size == 0:
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            raise ValueError(f"want max_new_tokens >= 1; got {max_new_tokens}")
        bucket = self._validate_request(p, max_new_tokens)
        rid = self._next_id
        self._next_id += 1
        self._queue.append(
            Request(rid, p, int(max_new_tokens), bucket,
                    profiling.Stopwatch(), trace_id=trace_id)
        )
        self._m_submitted.inc()
        self._m_queue_depth.set(len(self._queue))
        observability.instant(
            "serve/queued", id=rid, **self._trace_args(trace_id)
        )
        return rid

    def _trace_args(self, trace_id: Optional[str]) -> Dict:
        """Span/instant args for a trace id — empty when none, so
        engine-direct callers add no noise to the timeline.  When the
        front door names this engine's instance
        (:attr:`trace_instance`), every span carries it too — the
        fleet trace collector groups the merged timeline by that tag
        (pid=instance in Perfetto)."""
        args: Dict = {}
        if not observability.get_tracer().recording:
            return args  # nobody records the span: build nothing for it
        if trace_id:
            args["trace"] = trace_id
        if self.trace_instance:
            args["instance"] = self.trace_instance
        return args

    def _decode_trace_args(self, residents) -> Dict:
        """Decode chunks are batched: the span carries EVERY resident's
        trace id (comma-joined) so ONE Perfetto trace-id filter also
        surfaces the decode chunks a request was resident in."""
        args: Dict = {}
        if not observability.get_tracer().recording:
            return args  # nobody records the span: join nothing for it
        traces = ",".join(
            r.trace_id for r in residents if r.trace_id
        )
        if traces:
            args["traces"] = traces
        if self.trace_instance:
            args["instance"] = self.trace_instance
        return args

    @property
    def pending(self) -> int:
        return len(self._queue)

    @property
    def active(self) -> int:
        return sum(
            1 for s in self._slots
            if s is not None and s["mode"] == "decode"
        )

    @property
    def prefilling(self) -> int:
        return sum(
            1 for s in self._slots
            if s is not None and s["mode"] == "prefill"
        )

    # -- the serving loop -------------------------------------------------

    def run(self) -> List[Completion]:
        """Drain the queue: admit into free slots, decode in chunks,
        retire finished rows, re-admit — until every submitted request
        has completed.  Returns this call's completions in retirement
        order (also kept in :attr:`completions` by id)."""
        n0 = len(self._order)
        while self.tick():
            pass
        return self._order[n0:]

    def tick(self) -> bool:
        """ONE engine tick — admit + prefill, then a decode (or
        spec-verify) chunk — every part of it a stage of
        :attr:`loop_clock` (``znicz_serve_loop_seconds{stage}``).
        Returns False when there is no work (nothing ran).  Both
        :meth:`run` and the front door's engine thread drive the engine
        through this; where no front door opened the turn, the tick is
        the clock's iteration."""
        if not self._has_work():
            return False
        clock = self.loop_clock
        own_turn = not clock.running
        if own_turn:
            clock.start()
        try:
            if not self.active:
                # nothing decodes as the turn opens: whatever chunk ran
                # last, no period runs from it
                self._period_start = None
            with clock.stage("serve/schedule"):
                self._admit_pending()
            self._prefill_tick()
            if self.active:
                self._run_chunk()
            # a gauge of the turn, not a part of serving it: booked with
            # the front door's own gauges (which follow, behind a door)
            with clock.stage("frontdoor/housekeeping"):
                self._observe_residency()
        finally:
            if own_turn:
                clock.close_iteration()
                clock.stop()
        return True

    def _close_decode_period(self) -> None:
        """At the end of a decode or verify chunk's wait: the period
        since the chunk before, and the prefill chunks between them."""
        now = time.perf_counter()
        if self._period_start is not None:
            self._m_decode_period.observe(now - self._period_start)
            self._m_prefill_between.observe(self._prefill_since_decode)
        self._period_start = now
        self._prefill_since_decode = 0

    def _observe_residency(self) -> None:
        """What the rows resident after this tick cost in pool bytes a
        token: the blocks their tables reference, every kind, over the
        positions they have written."""
        tokens = 0
        for slot, st in enumerate(self._slots):
            if st is None:
                continue
            tokens += (
                int(self._pos[slot]) if st["mode"] == "decode"
                else min(
                    st["chunks_done"] * self.block_size, st["req"].prompt.size
                )
            )
        if tokens:
            self._m_bytes_per_token.observe(
                sum(k.referenced * k.block_bytes for k in self._kinds) / tokens
            )

    def _has_work(self) -> bool:
        return bool(self._queue) or self.active > 0 or self.prefilling > 0

    def _program(self, key: tuple) -> bool:
        """Ledger one executable per key: the compile-count hook's
        ground truth (tests cross-check it against the jit cache).
        Registry mirror: ``znicz_serve_compiles_total{kind,bucket}``
        counts TRUE first compiles per (params geometry, key) across the
        whole process — a second engine with the same geometry rides the
        shared jit caches and adds nothing.  ``key[1]`` is the block size
        for the prefill program, the chunk size for the decode program.
        Returns True exactly when this call IS a true first compile
        (the device-ledger hook in :meth:`_timed_program` keys off
        it, so ``/debug/programs`` stays count-identical to the
        counter)."""
        if key in self._programs:
            self._program_hits += 1
            self._m_program_hits.inc()
            return False
        self._programs[key] = 1
        full_key = (self._params_fp, key)
        if full_key in _COMPILED_KEYS:
            return False
        _COMPILED_KEYS.add(full_key)
        self._m_compiles.labels(kind=key[0], bucket=key[1]).inc()
        return True

    def _timed_program(self, key: tuple, fn, *args, **kwargs):
        """Ledger + invoke one compiled program.  On its TRUE first
        compile (process-wide, :meth:`_program`'s dedup) the call is
        wall-timed and recorded into the device ledger
        (``/debug/programs``, ``znicz_compile_seconds``,
        ``znicz_program_cost_*``) together with the lowering's cost
        analysis; steady-state invocations pay one dict lookup and
        nothing else.  The recorded wall time is the first dispatch —
        trace + compile + the first execution — which on a first
        compile is compile-dominated."""
        if not self._program(key):
            return fn(*args, **kwargs)
        cost = device_telemetry.lowered_cost(fn, args, kwargs)
        t0 = time.perf_counter()
        out = fn(*args, **kwargs)
        device_telemetry.record_program(
            key,
            time.perf_counter() - t0,
            cost=cost,
            dedup=(self._params_fp, key),
        )
        return out

    def _leave_queue(self, req: Request) -> None:
        """A slot takes ``req``: close out this spell of queueing."""
        waited = req.watch.elapsed() - req.last_queued_at
        req.timings.queue_s += waited
        self._m_queue_wait.observe(waited)

    def _retire(self, req: Request, emitted: List[int], reason: str):
        dt = req.watch.elapsed()
        comp = Completion(
            id=req.id,
            tokens=np.concatenate(
                [req.prompt, np.asarray(emitted, np.int32)]
            ),
            n_new=len(emitted),
            finish_reason=reason,
            latency_s=dt,
            tokens_per_sec=len(emitted) / max(dt, 1e-9),
            bucket=req.bucket,
            ttft_s=req.ttft_s,
            trace_id=req.trace_id,
            timings=req.timings.as_dict(),
        )
        self._order.append(comp)
        self.completions[req.id] = comp
        # feeds the shared registry histogram via the observe hook
        self.latency.record(dt)
        self._total_new += len(emitted)
        self._m_retired.labels(reason=reason).inc()
        self._m_tokens.inc(len(emitted))
        observability.instant(
            "serve/retired", id=req.id, reason=reason,
            **self._trace_args(req.trace_id),
        )

    # -- out-of-band retirement (cancellation / deadlines) ----------------

    def abort(self, request_id: int, reason: str) -> Optional[Completion]:
        """Retire a request OUT OF BAND with a typed completion —
        cancellation or deadline expiry, driven by the front door
        between ticks.  Works wherever the request currently lives:
        still queued (removed, zero tokens) or occupying a slot
        (tokens emitted so far are kept; the slot and its blocks are
        reclaimed immediately).  Returns the
        typed :class:`Completion`, or None when the id is unknown or
        already completed (the normal completion wins the race).

        NOT thread-safe: call only from the thread that drives the
        engine (the front door's engine thread)."""
        for i, req in enumerate(self._queue):
            if req.id == request_id:
                del self._queue[i]
                self._m_queue_depth.set(len(self._queue))
                # the whole wait so far was queueing: close it out so
                # the timings of a queued abort say where the time went
                req.timings.queue_s += (
                    req.watch.elapsed() - req.last_queued_at
                )
                self._retire(req, [], reason)
                return self.completions[request_id]
        for slot, st in enumerate(self._slots):
            if st is not None and st["req"].id == request_id:
                # the normal retire hook: completed full blocks publish
                # to the prefix cache (a cancelled request's prefix is
                # still reusable) and every table reference is released,
                # so the blocks are reclaimable the moment the typed
                # completion exists
                self._retire_slot(slot, list(st["emitted"]), reason)
                self._m_active.set(self.active)
                return self.completions[request_id]
        return None

    def reap(self, request_id: int) -> None:
        """Forget a completed request's record.  The front door copies
        each completion into its own handle as it collects it — keeping
        the engine-side ``completions``/retirement-order ledgers for
        every request ever served would leak on a long-lived service.
        Batch-style callers that use :meth:`run` never need this."""
        if self.completions.pop(request_id, None) is not None:
            self._order = [c for c in self._order if c.id != request_id]

    # -- capacity & the block allocator -----------------------------------

    @property
    def usable_blocks(self) -> int:
        """Pool capacity available to requests (null blocks excluded),
        every kind's blocks counted."""
        return sum(k.usable for k in self._kinds)

    def _update_pool_gauges(self) -> None:
        blocks = {"free": 0, "cached": 0, "used": 0}
        nbytes = dict(blocks)
        for kind in self._kinds:
            counts = (len(kind.free), len(kind.lru), kind.referenced)
            for state, n in zip(blocks, counts):
                blocks[state] += n
                nbytes[state] += n * kind.block_bytes
            self._m_in_use.labels(kind=kind.name).set(counts[2])
        for state in blocks:
            self._m_pool.labels(state=state).set(blocks[state])
            self._m_pool_bytes.labels(state=state).set(nbytes[state])

    def _slots_by_age(self) -> List[int]:
        """Occupied slot indices, oldest admission first — allocation
        runs in this order so seniority decides who survives pressure."""
        occ = [
            (self._slots[i]["seq"], i)
            for i in range(self.batch_size)
            if self._slots[i] is not None
        ]
        return [i for _, i in sorted(occ)]

    def _youngest_slot(self) -> int:
        return max(
            (i for i in range(self.batch_size) if self._slots[i] is not None),
            key=lambda i: self._slots[i]["seq"],
        )

    def _alloc_block(self, kind: _BlockKind) -> int:
        """One unreferenced, uncached block of ``kind``: free list
        first, then EVICT the least-recently-used cache-only block — the
        cache always yields before any live request is preempted.
        Returns -1 when both are dry (the caller preempts)."""
        faults.fire("pool.alloc")  # injected allocator failure (raises)
        if faults.fire("pool.pressure"):
            return -1  # injected exhaustion: free list AND cache "dry"
        if kind.free:
            return kind.free.pop()
        if kind.lru:
            blk, _ = kind.lru.popitem(last=False)
            h = kind.block_hash.pop(blk)
            del kind.cache[h]
            if kind.window is None:
                # every match a window kind's block of this hash could
                # serve runs through the block that just went
                for other in self._kinds:
                    if other.window is not None:
                        other.forget(h)
            self._n_evictions += 1
            self._m_prefix_evictions.inc()
            return blk
        return -1

    def _alloc_for(self, kind: _BlockKind, slot: int) -> Optional[int]:
        """One referenced block of ``kind`` for ``slot``, preempting the
        youngest occupant while the kind's pool (free list AND evictable
        cache) stays dry.  None when the starved slot was itself the
        youngest and got preempted (its request is back in the queue)."""
        while True:
            blk = self._alloc_block(kind)
            if blk >= 0:
                kind.incref(blk)
                return blk
            victim = self._youngest_slot()
            self._preempt(victim)
            if victim == slot:
                return None

    def _release_row(self, slot: int) -> None:
        """Drop every table reference of ``slot``, in every kind."""
        for kind in self._kinds:
            kind.release_row(slot)
        self._update_pool_gauges()

    def _preempt(self, slot: int) -> None:
        """Evict ``slot``: publish its completed full blocks into the
        prefix cache (cache-only blocks are the first thing allocation
        consumes, so a transient preemption often readmits straight out
        of its own just-cached prefix), release its table references
        and requeue its request at the queue HEAD (it is older than
        anything never admitted), to be recomputed on readmission.
        Refcounts keep any block a SURVIVOR also maps alive."""
        st = self._slots[slot]
        self._publish_row(slot)
        self._release_row(slot)
        self._slots[slot] = None
        self._done[slot] = True
        self._remaining[slot] = 0
        self._tok[slot] = 0
        self._pos[slot] = 0
        self._queue.appendleft(st["req"])
        req = st["req"]
        req.timings.preemptions += 1
        req.last_queued_at = req.watch.elapsed()
        self._n_preempted += 1
        self._m_preempted.inc()
        self._m_queue_depth.set(len(self._queue))
        observability.instant(
            "serve/preempt", id=req.id,
            **self._trace_args(req.trace_id),
        )

    def _ensure_blocks(self, slot: int, first_pos: int, last_pos: int) -> bool:
        """Make ``slot``'s tables serve queries at positions
        ``first_pos..last_pos``, kind by kind: a window kind first gives
        back the blocks that lie wholly behind ``first_pos``'s window
        (the table slides), then every kind grows to the block of
        ``last_pos``, preempting the youngest occupant whenever its pool
        is dry.  Returns False when the starved slot was itself the
        youngest and got preempted (its request is back in the queue)."""
        for kind in self._kinds:
            released = kind.drop_behind(slot, kind.first_needed(first_pos))
            if released:
                self._m_window_released.inc(released)
                observability.instant(
                    "serve/window_release", slot=slot, blocks=released,
                    kind=kind.name,
                )
            row = kind.row_blocks[slot]
            while kind.row_base[slot] + len(row) <= last_pos // self.block_size:
                blk = self._alloc_for(kind, slot)
                if blk is None:
                    return False
                kind.push(slot, blk)
        self._update_pool_gauges()
        return True

    def _cow_split(self, slot: int, j: int, *, copy: bool) -> bool:
        """Copy-on-write: retarget the entry of block index ``j`` of
        ``slot``'s table to a fresh private block before a write into a
        shared/cached block.
        ``copy=False`` when the impending write rewrites the whole
        block (a prefill chunk re-run) — the fresh block needs no
        content.  No-op for private blocks, and where the prefix cache is
        off (only a cached chain is ever shared).  False when allocation
        had to preempt ``slot`` itself.  A tower of several kinds never
        comes to a split: a match is whole blocks and stops short of the
        prompt's last token (:meth:`_cached_chain`), so a row's first own
        token opens a fresh block in every kind; a shared block in a
        write's way there is refused by type."""
        if not self.prefix_cache:
            return True
        if len(self._kinds) > 1:
            for kind in self._kinds:
                at = j - kind.row_base[slot]
                if 0 <= at < len(kind.row_blocks[slot]) and kind.shared(
                    int(kind.row_blocks[slot][at])
                ):
                    self._m_unsupported.labels(feature="prefix_cache").inc()
                    raise PrefixCacheUnsupportedError(
                        f"a write into a shared block of kind {kind.name!r}"
                        ": copy-on-write across kinds of cache blocks is "
                        "not served"
                    )
            return True
        kind = self._kinds[0]
        blk = int(kind.row_blocks[slot][j])
        if not kind.shared(blk):
            return True
        new = self._alloc_for(kind, slot)
        if new is None:
            return False
        if copy:
            self._pools = self._timed_program(
                ("cow", self.block_size),
                _cow_copy_prog,
                self._pools, jnp.int32(blk), jnp.int32(new),
            )
        kind.decref(blk)
        kind.row_blocks[slot][j] = new
        kind.tables[slot, j] = new
        self._n_cow += 1
        self._update_pool_gauges()
        return True

    # -- the prefix cache -------------------------------------------------

    def _chain_hashes(self, tokens: np.ndarray):
        """This pool's view of the shared keying scheme (see
        :func:`_chain_digests`): raw digests at this engine's block
        size."""
        yield from _chain_digests(tokens, self.block_size)

    def prefix_probe(self, prompt) -> Dict:
        """Public prefix-cache probe: the prompt's chained block keys
        (:func:`prefix_block_keys`) plus how many
        lead blocks are CURRENTLY resident in this engine's prefix
        cache (``cached_blocks`` is the longest cached chain prefix —
        exactly what admission would map).  Advisory: the cache mutates
        every tick, so the count is a snapshot, not a reservation.
        Safe to call from any thread (dict lookups only, no
        iteration)."""
        p = np.asarray(prompt, np.int32).reshape(-1)
        digests = list(_chain_digests(p, self.block_size))
        keys = [h.hex() for h in digests]
        cached = self._cached_chain(digests, p.size)[0]
        return {
            "prefix_cache": self.prefix_cache,
            "block_size": self.block_size,
            "block_keys": keys,
            "cached_blocks": cached,
            "cached_tokens": cached * self.block_size,
        }

    def _cached_chain(self, digests: List[bytes], size: int):
        """``(blocks, cut)``: how many leading whole blocks of a prompt of
        ``size`` tokens with chain hashes ``digests`` a new row can map —
        the longest chain at which EVERY kind holds its part, the kinds
        that keep every token all of it, a window kind the blocks a query
        at the chain's end still reads (:meth:`_BlockKind.first_needed`)
        — and whether a window kind that no longer held its part made it
        shorter than the other kinds' chain.  Where there are several
        kinds the chain stops short of the prompt's last token, so the
        final chunk runs into fresh blocks of every kind.  Dict lookups
        only."""
        if not self.prefix_cache:
            return 0, False
        several = len(self._kinds) > 1
        limit = (size - 1) // self.block_size if several else len(digests)
        longest = 0
        for h in digests[:limit]:
            if any(h not in k.cache for k in self._kinds if k.window is None):
                break
            longest += 1
        windows = [k for k in self._kinds if k.window is not None]
        n = longest
        while n and not all(
            h in k.cache
            for k in windows
            for h in digests[k.first_needed(n * self.block_size):n]
        ):
            n -= 1
        return n, n < longest

    def _chain_blocks(self, kind: _BlockKind, digests, n: int):
        """``(first index, blocks)``: ``kind``'s part of a cached chain of
        ``n`` blocks, as :meth:`_cached_chain` found it."""
        if not n:
            return 0, []
        first = kind.first_needed(n * self.block_size)
        return first, [kind.cache[h] for h in digests[first:n]]

    def _lookup_prefix(self, req: Request):
        """:meth:`_cached_chain` of the request's prompt: the blocks a row
        can map (full blocks only — a divergence mid-block misses from
        that block on) and whether a window kind cut them.  Claim-free:
        the caller bumps refcounts when it binds.  The hash chain is
        memoized on the request (content-pure); only the hash -> block
        resolution reads live state."""
        if not self.prefix_cache:
            return 0, False
        if req.digests is None:
            req.digests = list(self._chain_hashes(req.prompt))
        return self._cached_chain(req.digests, req.prompt.size)

    def _publish_row(self, slot: int) -> None:
        """Retire/preempt hook: publish this row's COMPLETED full
        blocks (every position holds a real token's K/V) into the
        prefix cache, kind by kind: a kind that keeps every token all of
        them, a window kind the ones the row still holds (those behind
        were given back: what stays serves a match that ends at a block
        boundary the row has reached, within the window of it).  First
        writer wins when two physical blocks hold the same content — the
        duplicate stays private and frees normally at release."""
        if not self.prefix_cache:
            return
        st = self._slots[slot]
        req = st["req"]
        emitted = st.get("emitted") or []
        if st["mode"] == "prefill":
            covered = min(
                st["chunks_done"] * self.block_size, req.prompt.size
            )
        else:
            # contiguous K/V coverage: the whole prompt plus every
            # emitted token EXCEPT the last (sampled, never fed back,
            # so its K/V was never written)
            covered = req.prompt.size + max(len(emitted) - 1, 0)
        n_full = min(
            [covered // self.block_size]
            + [k.row_base[slot] + len(k.row_blocks[slot]) for k in self._kinds]
        )
        if not n_full:
            return
        toks = np.concatenate(
            [req.prompt, np.asarray(emitted, np.int32)]
        )[: n_full * self.block_size]
        digests = list(self._chain_hashes(toks))
        for kind in self._kinds:
            base = kind.row_base[slot]
            for j in range(base, n_full):
                h, blk = digests[j], int(kind.row_blocks[slot][j - base])
                if h in kind.cache or blk in kind.block_hash:
                    continue  # already published (a mapped prefix), or dup
                kind.cache[h] = blk
                kind.block_hash[blk] = h

    def flush_prefix_cache(self) -> int:
        """Drop every cache entry; cache-only blocks return to the
        free list (blocks live requests still reference just lose their
        hash and free normally at release).  Returns entries dropped (a
        chain counts once: the first kind's)."""
        n = len(self._kinds[0].cache)
        for kind in self._kinds:
            kind.cache.clear()
            kind.block_hash.clear()
            kind.free.extend(kind.lru)
            kind.lru.clear()
        self._update_pool_gauges()
        return n

    # -- admission: chunked prefill ---------------------------------------

    def _admit_pending(self) -> None:
        # bind a queued request only when the pool can already hold the
        # UNCACHED part of its prompt beyond what in-flight prefills are
        # still owed (a prefix-cache hit consumes no allocation — the
        # blocks are already resident).  A fresh binding always carries
        # the youngest seq, so it can never evict anyone — prefilling
        # before the blocks exist would just starve, self-preempt and
        # requeue every tick, burning prefill compute and inflating the
        # preemption counter for no progress.
        # owed == 0 with the row still in prefill mode is exactly the
        # fully-cached case: its final chunk will COW-split one block.
        # Counted in EACH kind: a prompt holds at most a window kind's
        # table of blocks at once, whatever its length
        def owed(kind, req, held):
            return max(
                kind.most_held(req.bucket // self.block_size) - held, 1
            )

        reserved = [
            sum(
                owed(kind, s["req"], len(kind.row_blocks[i]))
                for i, s in enumerate(self._slots)
                if s is not None and s["mode"] == "prefill"
            )
            for kind in self._kinds
        ]
        for slot in range(self.batch_size):
            if self._slots[slot] is None and self._queue:
                req = self._queue[0]
                n_hit = self._lookup_prefix(req)[0]
                hits = [
                    self._chain_blocks(kind, req.digests, n_hit)[1]
                    for kind in self._kinds
                ]
                # a fully-cached prompt still COW-reruns its final
                # block's chunk for the first-token logits
                need = [
                    owed(kind, req, len(h)) for kind, h in zip(self._kinds, hits)
                ]
                # allocatable = free + evictable cache, NOT counting the
                # hit blocks themselves (binding pins them)
                pool = [
                    kind.allocatable - sum(1 for b in h if b in kind.lru)
                    for kind, h in zip(self._kinds, hits)
                ]
                if any(
                    p - r < n for p, r, n in zip(pool, reserved, need)
                ):
                    break
                reserved = [r + n for r, n in zip(reserved, need)]
                self._start_prefill(slot, self._queue.popleft())
        self._m_queue_depth.set(len(self._queue))
        self._m_active.set(self.active)

    def _start_prefill(self, slot: int, req: Request) -> None:
        """Bind a queued request to a slot: claim the longest cached
        block-chain prefix of its prompt (refcount bumps pin the blocks
        under the binder; a window kind's part goes into its ring at the
        blocks' own indices) and queue only the UNCACHED tail for chunked
        prefill.  Tail blocks are allocated and chunks run lazily by
        :meth:`_prefill_tick`, so binding itself can never stall or
        starve anyone.  Prompts anchor at position 0 and RIGHT-pad the
        final chunk to the block boundary — the prefix-cache alignment
        contract (see :func:`~znicz_tpu.workflow.generate
        .paged_prefill_chunk`)."""
        self._leave_queue(req)
        size = req.prompt.size
        # the prompt goes up ONCE, whole: its chunks are cut on the device
        tokens = np.full((1, self.prompt_width), self.pad_id, np.int32)
        tokens[0, :size] = req.prompt
        n_hit, cut = self._lookup_prefix(req)
        for kind in self._kinds:
            first, blocks = self._chain_blocks(kind, req.digests, n_hit)
            kind.row_base[slot] = first
            for blk in blocks:
                kind.incref(blk)
                if blk in kind.lru:
                    del kind.lru[blk]
                kind.push(slot, blk)
            if blocks:
                self._m_prefix_mapped.labels(kind=kind.name).inc(len(blocks))
        # a fully-cached prompt still needs its first-token LOGITS: the
        # final block's chunk re-runs (the write guard COW-splits it off
        # the shared block), so at least one chunk always executes
        skip = (
            n_hit - 1 if n_hit and n_hit * self.block_size == size else n_hit
        )
        req.timings.cached_tokens += skip * self.block_size
        self._m_prompt_tokens.inc(size)
        if self.prefix_cache:
            n_lookup = size // self.block_size
            self._n_prefix_hits += n_hit
            self._n_prefix_misses += n_lookup - n_hit
            self._n_cached_tokens += skip * self.block_size
            self._m_prefix_hits.inc(n_hit)
            self._m_prefix_misses.inc(n_lookup - n_hit)
            self._m_prefix_tokens.inc(skip * self.block_size)
            if n_hit:
                self._m_prefix_hit_requests.inc()
            if cut:
                self._m_prefix_cut.labels(reason="window_not_held").inc()
        self._slots[slot] = {
            "req": req, "emitted": [], "mode": "prefill",
            "seq": self._n_admits,
            "tokens": self._upload("prefill", tokens),
            "chunks_done": skip,
        }
        self._n_admits += 1
        self._done[slot] = True
        self._remaining[slot] = 0
        self._update_pool_gauges()

    def _prefill_tick(self) -> None:
        """Prompt chunks for prefilling slots, oldest first, under a
        per-tick TOKEN budget (Sarathi-style): the run loop alternates
        this with a decode chunk, so admission steals at most
        ``prefill_budget`` tokens' worth of tower work from the batch
        between decode chunks — a 2048-token prompt admits across a few
        bounded ticks instead of stalling everyone for one monolithic
        prefill.  Budget goes to the OLDEST prefill first (it finishes
        soonest and starts decoding).  While NOTHING is decoding there
        is nobody to stall, so the budget is waived and chunks run
        back-to-back."""
        budget = self.prefill_budget
        for slot in self._slots_by_age():
            while budget > 0 or self.active == 0:
                if not self._prefill_chunk_for(slot):
                    break
                budget -= self.block_size
        self._m_active.set(self.active)
        # a chunk's lap runs from the stage before it, so it holds the
        # blocks and the copy-on-write guard ahead of the call; this one
        # closes what followed the last call (the admission's
        # bookkeeping, a chunk that starved before its call)
        self.loop_clock.lap("serve/prefill/host")

    def _prefill_chunk_for(self, slot: int) -> bool:
        """Run one prefill chunk for ``slot``; True while the slot
        remains in prefill mode (False once admitted, retired,
        preempted, or idle)."""
        st = self._slots[slot]
        if st is None or st["mode"] != "prefill":
            return False  # preempted mid-tick, or already decoding
        faults.fire("engine.prefill")
        req = st["req"]
        size = req.prompt.size
        c = st["chunks_done"]
        if not self._ensure_blocks(
            slot, c * self.block_size, (c + 1) * self.block_size - 1
        ):
            return False  # starved AND youngest: requeued itself
        # a prefill chunk rewrites its whole block: when the target is
        # a mapped cached block (the fully-cached-prompt re-run for
        # first-token logits) COW-split it — copy-free, every slot is
        # about to be overwritten — so shared content stays pristine
        if not self._cow_split(slot, c, copy=False):
            return False
        last = c == req.bucket // self.block_size - 1
        # FIRST admission only: a preemption-recompute's final chunk
        # traces as serve/prefill and re-fires nothing, keeping the
        # one-serve/admit-span-per-request invariant (and the
        # admitted/TTFT series) exact under preemption
        first_time = req.id not in self._admitted_ids
        greedy, top_k, nucleus = self._structure
        clock = self.loop_clock
        t0 = time.perf_counter()
        # the LAST chunk is the admission event (first token sampled);
        # earlier chunks trace as serve/prefill
        with self.timer.phase(
            "admit" if last and first_time else "prefill",
            request=req.id, bucket=req.bucket, chunk=c,
            **self._trace_args(req.trace_id),
        ):
            with clock.stage("serve/prefill/host"):
                where = np.array(
                    [
                        c * self.block_size,
                        (size - 1) % self.block_size
                        if last
                        else self.block_size - 1,
                        st["seq"],
                    ],
                    np.int32,
                )
                self._pools, first, *load = self._timed_program(
                    ("prefill", self.block_size, self._structure),
                    _paged_prefill_prog,
                    self.params, self._pools, self._row_tables(slot),
                    st["tokens"], self._upload("prefill", where),
                    self._temperature, self._top_p, self._rng,
                    block_size=self.block_size, n_heads=self.n_heads,
                    greedy=greedy, top_k=top_k, nucleus=nucleus,
                    moe_top_k=self.moe_top_k,
                    moe_dispatch=self.moe_dispatch, model=self.model,
                )
                self._load_backlog.extend(load)
                st["chunks_done"] = c + 1
            if last:
                # the one place a prefill chunk waits for the device,
                # and with it for every chunk dispatched ahead of it
                with clock.stage("serve/prefill/wait"):
                    first, loads = self._read("prefill", first)
                    first = int(first)
                    self._count_loads("prefill", loads, 1)
        req.timings.prefill_s += time.perf_counter() - t0
        self._m_prefill_chunks.inc()
        self._prefill_since_decode += 1
        if not last:
            return True
        if first_time:
            self._admitted_ids.add(req.id)
            req.ttft_s = req.watch.elapsed()
            self._m_admitted.inc()
            self._m_ttft.observe(req.ttft_s)
        if first == self.eos_id:
            self._retire_slot(slot, [first], "eos")
        elif req.max_new_tokens == 1:
            self._retire_slot(slot, [first], "budget")
        else:
            st["mode"] = "decode"
            del st["tokens"]  # the prompt's device copy has done its work
            st["emitted"] = [first]
            self._tok[slot] = first
            self._pos[slot] = size
            self._done[slot] = False
            self._remaining[slot] = req.max_new_tokens - 1
        return False

    def _retire_slot(self, slot: int, emitted: List[int], reason: str):
        self._slots[slot]["emitted"] = emitted
        self._publish_row(slot)
        self._retire(self._slots[slot]["req"], emitted, reason)
        self._release_row(slot)
        self._slots[slot] = None
        self._done[slot] = True
        self._remaining[slot] = 0
        # zero the stale row state so an idle slot can never index past
        # a narrowed decode window
        self._tok[slot] = 0
        self._pos[slot] = 0

    def _grow_for_chunk(self, steps_for) -> bool:
        """Pre-chunk allocation + write guard, oldest first: each
        decoding row gets blocks covering the ``steps_for(slot)``
        positions the coming chunk may write — never the whole budget
        up front; exhaustion preempts the youngest occupant.  A write
        must never land in a shared/cached block: COW-split (with copy
        — the block holds earlier positions' live K/V) any write-range
        block still shared.  Structurally unreachable under
        block-aligned sharing + publish-at-retire (mapped blocks are
        full, writes land past them), but the guard keeps the invariant
        under ANY future publish policy.  Returns False when pressure
        preempted every decoder."""
        for slot in self._slots_by_age():
            st = self._slots[slot]
            if st is None or st["mode"] != "decode":
                continue
            p0 = int(self._pos[slot])
            last_pos = p0 + max(int(steps_for(slot)) - 1, 0)
            if not self._ensure_blocks(slot, p0, last_pos):
                continue  # starved AND youngest: requeued itself
            for j in range(
                p0 // self.block_size, last_pos // self.block_size + 1
            ):
                if self._slots[slot] is None:
                    break  # a COW allocation preempted this very row
                if not self._cow_split(slot, j, copy=True):
                    break
        return self.active > 0

    def _decode_window(self) -> int:
        """The decode/verify gather WINDOW: the x2 rung covering the
        blocks active rows actually hold in a kind that keeps every token
        (a window kind's ring is passed whole) — the compiled-variant count
        stays logarithmic and short requests never pay ``T_max``-wide
        attention (docs/SERVING.md)."""
        need = max(
            (len(kind.row_blocks[i]) for kind in self._kinds
             if kind.window is None
             for i, s in enumerate(self._slots)
             if s is not None and s["mode"] == "decode"),
            default=1,
        )
        window = 1
        while window < need:
            window *= 2
        return min(window, self.blocks_per_row)

    def _row_tables(self, slot: int):
        """``slot``'s table as the prefill program takes it: the one
        kind's, or ``{kind: table}`` where the tower declares kinds."""
        self._m_crossings["prefill", "up"].inc(len(self._kinds))
        if not self._by_kind:
            return jnp.asarray(self._kinds[0].tables[slot])
        return {k.name: k.device_table(k.tables[slot]) for k in self._kinds}

    def _batch_tables(self, window: int, program: str = "decode"):
        """Every slot's table as the decode and verify programs take
        them: cut to the ``window`` rung where the kind keeps every token
        (:meth:`_decode_window`); a window kind's ring whole, which is one
        width for any stream."""
        self._m_crossings[program, "up"].inc(len(self._kinds))

        def cut(kind):
            return kind.device_table(
                kind.tables[:, :window] if kind.window is None
                else kind.tables
            )

        if not self._by_kind:
            return cut(self._kinds[0])
        return {k.name: cut(k) for k in self._kinds}

    def _count_gathered(self, steps: int, window: int, load=None) -> None:
        """What the chunk's layers read of the cache.  ``load``: the
        tower's load sums, where it reports any.  Their ``cached_rows``
        is the cached rows a layer of it read over the chunk's steps (a
        tower of several kinds: the MEAN over its layers, so that steps x
        layers x this is what the tower read, as for every other tower;
        ``cached_rows_by_kind`` has a layer of each kind, and ``attended
        _rows_by_kind`` the rows its queries met, which is more where
        several rows read shared blocks once).  The classic
        K/V tower reports none: its gather reads every slot's window,
        active or not."""
        load = load or {}
        read = load.get("cached_rows")
        self._m_decode_steps.inc(steps)
        self._m_decode_gathered.inc(
            steps * self.batch_size * window * self.block_size
            if read is None else int(read)
        )
        for kind, rows in load.get("cached_rows_by_kind", {}).items():
            self._m_decode_cached_rows.labels(kind=kind).inc(int(rows))
        for kind, rows in load.get("attended_rows_by_kind", {}).items():
            self._m_decode_attended_rows.labels(kind=kind).inc(int(rows))
        self._m_decode_chunks.labels(window=window).inc()

    # -- speculative decoding: draft -> verify -> accept -> rollback ------

    def _draft_pending(self) -> Dict[int, np.ndarray]:
        """One drafting pass over the decoding rows: each row's drafter
        context is its OWN prompt plus everything it has emitted (so
        self-repeating generations draft well, not just repetitive
        prompts), clamped so accepted drafts can never outrun the
        row's remaining budget.  Returns {} when NO row drafted —
        the tick then runs the plain decode chunk instead of paying
        for an all-pad verify."""
        drafts: Dict[int, np.ndarray] = {}
        any_draft = False
        for slot, st in enumerate(self._slots):
            if st is None or st["mode"] != "decode":
                continue
            req = st["req"]
            rem = req.max_new_tokens - len(st["emitted"])
            k = min(self.spec_k, rem - 1)
            d = np.zeros((0,), np.int32)
            if k > 0:
                ctx = np.concatenate(
                    [req.prompt, np.asarray(st["emitted"], np.int32)]
                )
                d = np.asarray(
                    self.drafter.propose(ctx, k), np.int32
                ).reshape(-1)[:k]
            drafts[slot] = d
            any_draft = any_draft or d.size > 0
        return drafts if any_draft else {}

    def _verify_chunk(self, drafts: Dict[int, np.ndarray]) -> None:
        """One speculative tick: pack every decoding row's last token +
        drafted continuation into a [B, W] verify batch (W = the
        drafted max snapped UP the ``spec_buckets`` ladder — accepted
        and drafted lengths are traced, so no stream ever compiles a
        program per length), run ONE bucketed verify program, emit each
        row's longest agreeing prefix plus the bonus token, and ROLL
        BACK the rest by truncating the block table — refcounts reclaim
        the rejected blocks, no copies (docs/SERVING.md "Speculative
        decoding")."""
        clock = self.loop_clock
        with clock.stage("serve/verify/prepare"):
            w = bucket_for(
                max(d.size for d in drafts.values()) + 1, self.spec_buckets
            )
            # the call's one operand beside the tables, and views of it
            # (:func:`_paged_verify_prog`'s ``batch``); pos, done and the
            # chunk's index are filled in after the rows have grown
            batch = np.zeros((self.batch_size, w + 5), np.int32)
            tokens, n_write, draft_len = (
                batch[:, :w], batch[:, w + 2], batch[:, w + 3]
            )
            tokens[:] = self.pad_id
            for slot, d in drafts.items():
                st = self._slots[slot]
                req = st["req"]
                rem = req.max_new_tokens - len(st["emitted"])
                dl = min(d.size, w - 1, max(rem - 1, 0))
                tokens[slot, 0] = self._tok[slot]
                tokens[slot, 1:1 + dl] = d[:dl]
                draft_len[slot] = dl
                # only positions 0..dl are ever READ back (t0 + accepted
                # drafts; the bonus token's K/V is the next tick's
                # write): masking the bucket pad in-program both avoids
                # garbage writes and keeps _grow_for_chunk from
                # allocating — and possibly preempting a younger row for
                # — blocks that this same tick's rollback would hand
                # straight back
                n_write[slot] = dl + 1
        with clock.stage("serve/verify/grow"):
            if not self._grow_for_chunk(lambda slot: int(n_write[slot])):
                return  # allocation pressure preempted every decoder
            self._peak_active = max(self._peak_active, self.active)
            window = self._decode_window()
            residents = [
                s["req"] for s in self._slots
                if s is not None and s["mode"] == "decode"
            ]
        t0 = time.perf_counter()
        with self.timer.phase(
            "verify", active=self.active, width=w,
            **self._decode_trace_args(residents),
        ):
            with clock.stage("serve/verify/prepare"):
                batch[:, w], batch[:, w + 1] = self._pos, self._done
                batch[0, w + 4] = self._chunk_idx
                self._chunk_idx += 1
                greedy, top_k, nucleus = self._structure
                operands = (
                    self._batch_tables(window, "verify"),
                    self._upload("verify", batch),
                )
            with clock.stage("serve/verify/dispatch"):
                pools, out, n_acc = self._timed_program(
                    ("spec_verify", w, self.batch_size, window,
                     self._structure),
                    _paged_verify_prog,
                    self.params, self._pools, *operands,
                    self._temperature, self._top_p, self._rng,
                    width=w, block_size=self.block_size,
                    n_heads=self.n_heads, greedy=greedy, top_k=top_k,
                    nucleus=nucleus, moe_top_k=self.moe_top_k,
                    moe_dispatch=self.moe_dispatch,
                )
                self._pools = pools
            with clock.stage("serve/verify/wait"):
                (out, n_acc), loads = self._read("verify", (out, n_acc))
            self._close_decode_period()
            with clock.stage("serve/verify/fetch"):
                self._count_loads("prefill", loads, 1)
        dt = time.perf_counter() - t0
        with clock.stage("serve/verify/emit"):
            self._n_verify_steps += 1
            self._count_gathered(1, window)
            for r in residents:
                r.timings.decode_s += dt
            for slot, st in enumerate(self._slots):
                # rows preempted during allocation never reached the
                # program (their writes were masked via the done flag)
                if st is None or st["mode"] != "decode":
                    continue
                req, emitted = st["req"], st["emitted"]
                dl = int(draft_len[slot])
                na = min(int(n_acc[slot]), dl)
                reason = None
                appended = 0
                for t in out[slot, :na + 1]:
                    emitted.append(int(t))
                    appended += 1
                    if int(t) == self.eos_id:
                        reason = "eos"
                        break
                    if len(emitted) >= req.max_new_tokens:
                        reason = "budget"
                        break
                self._n_spec_drafted += dl
                self._n_spec_accepted += na
                self._n_spec_rejected += dl - na
                req.timings.spec_drafted += dl
                req.timings.spec_accepted += na
                if dl:
                    self._m_spec_drafted.inc(dl)
                    self._m_spec_accepted.inc(na)
                    self._m_spec_rejected.inc(dl - na)
                    self._m_spec_accept_len.observe(float(na))
                if reason is not None:
                    self._retire_slot(slot, emitted, reason)
                else:
                    self._tok[slot] = emitted[-1]
                    self._pos[slot] = int(self._pos[slot]) + appended
                    self._remaining[slot] = req.max_new_tokens - len(emitted)
                    self._truncate_row(slot)
            self._m_active.set(self.active)

    def _truncate_row(self, slot: int) -> None:
        """Speculative ROLLBACK: drop every kind's table entries past the
        last position holding accepted K/V.  The truncated blocks were
        allocated (private, COW-guarded) for draft positions the
        verifier rejected — a decref walks each back to the free list
        (or the cache, had it been shared), so rollback is bookkeeping
        only: no device copies, no recompute."""
        keep = (int(self._pos[slot]) - 1) // self.block_size + 1
        for kind in self._kinds:
            kind.drop_past(slot, keep)
        self._update_pool_gauges()

    def _run_chunk(self) -> None:
        """One decode chunk, or one verify chunk where a row drafted."""
        faults.fire("engine.decode_step")
        clock = self.loop_clock
        if self.spec_k:
            with clock.stage("serve/verify/draft"):
                drafts = self._draft_pending()
            if drafts:
                self._verify_chunk(drafts)
                return
            # no row produced a draft this tick: fall through to the
            # plain (already-compiled) decode chunk — an unpredictable
            # stream pays ZERO verify overhead and ZERO new programs
        with clock.stage("serve/decode/grow"):
            # lazy per-chunk allocation, oldest first: each decoding row
            # gets blocks covering the positions THIS chunk can write
            # (min(chunk, remaining) steps) — never the whole budget up
            # front; exhaustion preempts the youngest occupant
            if not self._grow_for_chunk(
                lambda slot: min(
                    self.admit_every, int(self._remaining[slot])
                )
            ):
                return  # allocation pressure preempted every decoder
            self._peak_active = max(self._peak_active, self.active)
            # decode WINDOW (:meth:`_decode_window`): allocation above
            # already covers this chunk's growth, so the window cannot
            # be outrun mid-chunk; retired/idle rows were zeroed and
            # write to the null block regardless.
            window = self._decode_window()
            residents = [
                s["req"] for s in self._slots
                if s is not None and s["mode"] == "decode"
            ]
        t0 = time.perf_counter()
        with self.timer.phase(
            "decode", active=self.active,
            **self._decode_trace_args(residents),
        ):
            with clock.stage("serve/decode/prepare"):
                self._state[_CHUNK, 0] = self._chunk_idx
                self._chunk_idx += 1
                greedy, top_k, nucleus = self._structure
                operands = (
                    self._batch_tables(window),
                    self._upload("decode", self._state.copy()),
                )
            with clock.stage("serve/decode/dispatch"):
                (pools, tok, pos, done, remaining, out, steps, *load) = (
                    self._timed_program(
                        ("paged_chunk", self.admit_every, self.batch_size,
                         window, self._structure),
                        _paged_decode_chunk,
                        self.params, self._pools, *operands,
                        self._temperature, self._top_p, self._rng,
                        chunk=self.admit_every,
                        block_size=self.block_size, t_max=self.t_max,
                        n_heads=self.n_heads, eos_id=self.eos_id,
                        greedy=greedy, top_k=top_k, nucleus=nucleus,
                        moe_top_k=self.moe_top_k,
                        moe_dispatch=self.moe_dispatch, model=self.model,
                    )
                )
                self._pools = pools
            # the chunk's one blocking read: it also waits for every
            # prefill chunk dispatched ahead of the chunk
            with clock.stage("serve/decode/wait"):
                (out, steps, load, *rows), loads = self._read(
                    "decode", (out, steps, load, tok, pos, done, remaining)
                )
            self._close_decode_period()
            # everything is on the host by now: counters and copies
            with clock.stage("serve/decode/fetch"):
                steps = int(steps)
                self._count_loads("prefill", loads, 1)
                self._count_loads("decode", load, steps)
                self._state[:_CHUNK] = rows
        dt = time.perf_counter() - t0
        with clock.stage("serve/decode/emit"):
            self._count_gathered(steps, window, load[0] if load else None)
            for r in residents:
                r.timings.decode_s += dt
            for slot, st in enumerate(self._slots):
                if st is None or st["mode"] != "decode":
                    continue
                req, emitted = st["req"], st["emitted"]
                reason = None
                for t in out[slot, :steps]:
                    emitted.append(int(t))
                    if int(t) == self.eos_id:
                        reason = "eos"
                        break
                    if len(emitted) >= req.max_new_tokens:
                        reason = "budget"
                        break
                if reason is not None:
                    self._retire_slot(slot, emitted, reason)
            self._m_active.set(self.active)

    # -- introspection ----------------------------------------------------

    def compile_stats(self) -> Dict:
        """Compile-count hook: ``programs`` holds one ``("prefill",
        block_size, structure)`` entry plus one ``("paged_chunk", chunk,
        B, window, structure)`` entry per x2 window rung the stream's
        occupancy ever reached — logarithmic in T_max/block_size,
        independent of request count; ``program_hits`` counts
        invocations that reused one.  ``*_jit_entries`` are the
        process-wide jax caches backing them (shared across engines: a
        second engine with the same geometry compiles nothing new)."""
        return {
            "programs": dict(self._programs),
            "n_programs": len(self._programs),
            "program_hits": self._program_hits,
            "prefill_jit_entries": _paged_prefill_prog._cache_size(),
            "paged_chunk_jit_entries": _paged_decode_chunk._cache_size(),
            "cow_jit_entries": _cow_copy_prog._cache_size(),
            "spec_verify_jit_entries": _paged_verify_prog._cache_size(),
        }

    @property
    def pool_free_frac(self) -> float:
        """Fraction of the pool still ALLOCATABLE (free list plus
        evictable cache-only blocks), of the kind that has least left —
        the one owner of the formula the front door's pool-pressure
        watermark reads."""
        return min(k.allocatable / max(k.usable, 1) for k in self._kinds)

    def spec_stats(self) -> Dict:
        """The live speculative-decoding report (``stats()["spec"]``):
        drafted/accepted/rejected token tallies, verify-step count and
        the acceptance rate — accepted drafts over drafted, the single
        number that says whether speculation is paying on this
        stream."""
        return {
            "enabled": bool(self.spec_k),
            "k": self.spec_k,
            "buckets": list(self.spec_buckets),
            "drafted": self._n_spec_drafted,
            "accepted": self._n_spec_accepted,
            "rejected": self._n_spec_rejected,
            "verify_steps": self._n_verify_steps,
            "acceptance_rate": round(
                self._n_spec_accepted / max(self._n_spec_drafted, 1), 4
            ),
        }

    def stats(self) -> Dict:
        """Serving report: completions, generated tokens, the per-request
        latency aggregate, per-phase host timings, compile counts, the
        speculative-decoding sub-dict (:meth:`spec_stats`) and the
        block-pool + prefix-cache view.  ``peak_active`` is the max rows
        decoding in one chunk — the engine's observed concurrency.
        ``pool_blocks_free`` counts ALLOCATABLE blocks — the free list
        plus evictable cache-only blocks (``pool_blocks_cached``); a
        cached block a live request also maps counts as used."""
        return {
            "kv_backend": "paged",
            "completed": len(self.completions),
            "generated_tokens": self._total_new,
            "peak_active": self._peak_active,
            "latency": self.latency.summary(),
            "phases": self.timer.summary(),
            "spec": self.spec_stats(),
            **self.compile_stats(),
            "pool_blocks": self.usable_blocks,
            "pool_blocks_free": sum(k.allocatable for k in self._kinds),
            "pool_blocks_cached": sum(len(k.lru) for k in self._kinds),
            "block_size": self.block_size,
            "block_bytes": self.block_bytes,
            "pool_bytes": sum(k.usable * k.block_bytes for k in self._kinds),
            "kinds": {
                k.name: {
                    "window": k.window, "table_width": k.width,
                    "blocks": k.usable, "blocks_free": k.allocatable,
                    "block_bytes": k.block_bytes,
                    "blocks_released_behind_window": k.n_released,
                }
                for k in self._kinds
            },
            "preemptions": self._n_preempted,
            "prefix_cache": {
                "enabled": self.prefix_cache,
                "entries": len(self._kinds[0].cache),
                "hits": self._n_prefix_hits,
                "misses": self._n_prefix_misses,
                "cached_tokens": self._n_cached_tokens,
                "evictions": self._n_evictions,
                "cow_splits": self._n_cow,
            },
        }
