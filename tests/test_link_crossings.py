"""A serving program call crosses the host-device link once each way
(PR 39).

What a call sends is a block table a kind and ONE packed int32 array (the
rows' state and the chunk's index for a decode or verify chunk; offset,
last and seq for a prefill chunk, whose prompt went up once, whole, at
admission); what it reads comes down in ONE ``jax.device_get``.
``znicz_serve_link_crossings_total{program,direction}`` counts both at the
engine's call sites.  The rng key is folded inside the programs: a seeded
sampled stream is what a loop that folds it on the host, as the engine did
before, serves; and what a call was handed is its own, so the host may
write its state the moment the call returns.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_window_gqa_lm import Toy as WindowToy, _counter
from znicz_tpu.core import prng
from znicz_tpu.services import engine
from znicz_tpu.workflow.generate import (
    _sample,
    init_paged_kv,
    paged_decode_step,
    paged_prefill_chunk,
)
from znicz_tpu.workflow.transformer import init_lm_params

BLOCK, CHUNK, HEADS, VOCAB, EOS = 8, 4, 4, 61, 0
CROSSINGS = "znicz_serve_link_crossings_total"
PROGRAMS = {
    "prefill": "_paged_prefill_prog", "decode": "_paged_decode_chunk",
    "verify": "_paged_verify_prog",
}


@pytest.fixture(scope="module")
def params():
    prng.seed_all(39)
    return init_lm_params(VOCAB, 32, 2, HEADS, max_seq=128)


def _classic(params, **kw):
    kw = {
        "n_heads": HEADS, "eos_id": EOS, "batch_size": 2, "block_size": BLOCK,
        "admit_every": CHUNK, "max_seq": 128, **kw,
    }
    return engine.PagedDecodeEngine(params, **kw)


def _prompts(seed, lengths, vocab=VOCAB):
    gen = np.random.default_rng(seed)
    return [gen.integers(1, vocab, n).astype(np.int32) for n in lengths]


def _spy_on_programs(monkeypatch, after_dispatch=None):
    """Count the calls of the three programs; ``after_dispatch(name,
    args)`` runs when a call has returned and its outputs are still
    unread."""
    calls = dict.fromkeys(PROGRAMS, 0)
    for program, name in PROGRAMS.items():
        real = getattr(engine, name)

        def spy(*args, _real=real, _program=program, **kwargs):
            out = _real(*args, **kwargs)
            calls[_program] += 1
            if after_dispatch is not None:
                after_dispatch(_program, args)
            return out

        spy._cache_size = real._cache_size
        monkeypatch.setattr(engine, name, spy)
    return calls


def _crossings():
    return {
        (program, direction): _counter(
            CROSSINGS, program=program, direction=direction
        )
        for program in PROGRAMS for direction in ("up", "down")
    }


class EveryOtherDrafter:
    """Drafts (junk) at every other tick, so that a stream runs verify
    chunks and plain decode chunks both."""

    def __init__(self):
        self.calls = 0

    def propose(self, context, k):
        self.calls += 1
        return np.full((k if self.calls % 2 else 0,), 1, np.int32)


@pytest.mark.parametrize(
    "build, prompts, kinds",
    [
        (lambda p: _classic(p), lambda: _prompts(1, (5, 19, 11)), 1),
        (
            lambda p: WindowToy().engine(),
            lambda: _prompts(2, (5, 22, 13), 30), 2,
        ),
        (
            lambda p: _classic(p, spec_k=3, drafter=EveryOtherDrafter()),
            lambda: _prompts(6, (9,)), 1,
        ),
    ],
    ids=["classic", "window", "verify"],
)
def test_a_call_sends_a_table_a_kind_and_one_array_and_reads_once(
    build, prompts, kinds, params, monkeypatch
):
    eng = build(params)
    prompts = prompts()
    calls = _spy_on_programs(monkeypatch)
    before = _crossings()
    for p in prompts:
        eng.submit(p, 10)
    assert len(eng.run()) == len(prompts)
    got = {k: v - before[k] for k, v in _crossings().items()}
    assert calls["prefill"] >= len(prompts) and calls["decode"] > 0
    assert calls["verify"] > 0 or not eng.spec_k
    assert eng.stats()["preemptions"] == 0  # each prompt was admitted once
    for program in ("decode", "verify"):
        assert got[program, "up"] == calls[program] * (1 + kinds), program
        assert got[program, "down"] == calls[program], program
    # a chunk: its table(s) and where it stands; a prompt: its tokens, once,
    # and the one read of its first token
    assert got["prefill", "up"] == (
        calls["prefill"] * (1 + kinds) + len(prompts)
    )
    assert got["prefill", "down"] == len(prompts)


# -- the key is folded where it is used -----------------------------------


def _host_folded_stream(params, prompt, new_tokens, rng, *, seq, first_chunk,
                        temperature, top_p):
    """One request served alone by a loop that folds the key on the HOST
    for every call, as the engine did before PR 39: ``fold_in(rng, seq)``
    for the prompt's first token, ``fold_in(fold_in(rng, 1 << 20 |
    chunk), step)`` inside decode chunk ``chunk``."""
    sample = jax.jit(_sample, static_argnums=(3, 4))
    temperature, top_p = jnp.float32(temperature), jnp.float32(top_p)
    width = -(-prompt.size // BLOCK) * BLOCK
    padded = np.zeros((1, width), np.int32)
    padded[0, :prompt.size] = prompt
    n_row = -(-(width + new_tokens) // BLOCK)
    pools = init_paged_kv(params, n_row + 1, BLOCK)
    table = jnp.arange(1, n_row + 1, dtype=jnp.int32)
    tower = dict(n_heads=HEADS, block_size=BLOCK)
    for c in range(width // BLOCK):
        final = c == width // BLOCK - 1
        pools, logits = paged_prefill_chunk(
            params, pools, table,
            jnp.asarray(padded[:, c * BLOCK:(c + 1) * BLOCK]),
            jnp.int32(c * BLOCK),
            last=jnp.int32((prompt.size - 1) % BLOCK if final else BLOCK - 1),
            **tower,
        )
    tok = sample(
        logits, jax.random.fold_in(rng, seq), temperature, 0, True, top_p
    )
    out, pos, chunk = [int(tok[0])], prompt.size, first_chunk
    while len(out) < new_tokens and out[-1] != EOS:
        key = jax.random.fold_in(rng, 1 << 20 | chunk)
        for step in range(CHUNK):
            pools, logits = paged_decode_step(
                params, pools, table[None], tok, jnp.asarray([pos], jnp.int32),
                **tower,
            )
            tok = sample(
                logits, jax.random.fold_in(key, step), temperature, 0, True,
                top_p,
            )
            out.append(int(tok[0]))
            pos += 1
            if len(out) == new_tokens or out[-1] == EOS:
                break
        chunk += 1
    return out


def test_a_sampled_stream_is_what_a_host_folded_key_serves(
    params, monkeypatch
):
    """Prefill's first token and the decode chunks' tokens, for two
    requests served one after the other by one engine (the second's seq is
    1 and its first decode chunk follows the first request's); and once the
    programs are compiled the engine's thread folds no key."""
    rng, sampling = jax.random.key(39), dict(temperature=0.9, top_p=0.85)
    eng = _classic(params, batch_size=1, rng=rng, **sampling)
    first_chunk = 0
    for seq, (prompt, new_tokens) in enumerate(
        zip(_prompts(3, (13, 24)), (11, 9))
    ):
        if seq == 1:
            def no_fold(*args, **kwargs):
                raise AssertionError("the key is folded on the host")

            monkeypatch.setattr(jax.random, "fold_in", no_fold)
        eng.submit(prompt, new_tokens)
        (done,) = eng.run()
        monkeypatch.undo()
        want = _host_folded_stream(
            params, prompt, new_tokens, rng, seq=seq, first_chunk=first_chunk,
            **sampling,
        )
        assert done.tokens[prompt.size:].tolist() == want
        assert len(want) > 1 + CHUNK  # more than one decode chunk compared
        first_chunk = eng._chunk_idx


# -- what a call was handed is its own ------------------------------------


def _aligned(array):
    """A copy of ``array`` at a 64-byte boundary: what the CPU backend
    takes as a device buffer in place, without a copy of its own."""
    raw = np.empty(array.nbytes + 64, np.uint8)
    start = -raw.ctypes.data % 64
    out = raw[start:start + array.nbytes].view(array.dtype)
    out = out.reshape(array.shape)
    out[...] = array
    return out


@pytest.mark.parametrize("tower", ["classic", "window"])
def test_the_host_may_write_its_state_while_a_chunk_is_in_flight(
    tower, params, monkeypatch
):
    """On the CPU a device array may BE the host's memory: the packed
    state is a fresh buffer a call and a ring's table a copy, so writing
    ``_tok``, ``_pos`` or the ring right after a dispatch changes nothing
    of the chunk in flight."""
    def build():
        if tower == "classic":
            return _classic(params), _prompts(4, (5, 19))
        return WindowToy().engine(), _prompts(5, (5, 22), 30)

    def serve(eng, prompts):
        for p in prompts:
            eng.submit(p, 12)
        return {c.id: c.tokens.tolist() for c in eng.run()}

    want = serve(*build())
    eng, prompts = build()
    # the worst case: the host's arrays lie where the backend would alias
    eng._state = _aligned(eng._state)
    eng._tok, eng._pos, eng._done, eng._remaining = eng._state[:4]
    for kind in eng._kinds:
        kind.tables = _aligned(kind.tables)
    rings = [k for k in eng._kinds if k.window is not None]
    assert bool(rings) == (tower == "window")
    handed = []

    def scribble(program, args):
        if program != "decode":
            return
        tables, state = args[2], args[3]
        assert not np.shares_memory(np.asarray(state), eng._state)
        for kind in rings:
            assert not np.shares_memory(
                np.asarray(tables[kind.name]), kind.tables
            )
        kept = [eng._state.copy()] + [k.tables.copy() for k in rings]
        eng._tok[:], eng._pos[:] = 1, 3
        for kind in rings:
            kind.tables[:] = 1
        handed.append(kept)

    real = engine.PagedDecodeEngine._read

    def read(self, program, outputs):
        got = real(self, program, outputs)  # the chunk has finished
        if program == "decode":
            state, *tables = handed.pop()
            eng._state[:] = state
            for kind, table in zip(rings, tables):
                kind.tables[:] = table
        return got

    _spy_on_programs(monkeypatch, after_dispatch=scribble)
    monkeypatch.setattr(engine.PagedDecodeEngine, "_read", read)
    assert serve(eng, prompts) == want
    assert not handed
