"""What ``benchmarks/`` reads from the serving program, held in tier-1.

``benchmarks/tests/`` is not part of tier-1, so a refactor of the engine
would otherwise learn of these pins on the chip: device traces are reduced
by the programs' names (``jit__paged_decode_chunk`` /
``jit__paged_prefill_prog``), ``benchmarks/tests/test_broken_path.py`` and
``test_axk1_cell.py`` / ``test_smallthinker_cell.py`` wrap
``engine._paged_decode_chunk`` and unpack its 7 (classic tower) or 8 (a
``model=`` tower) values, the drivers and ``chip_smoke.py`` build
``PagedDecodeEngine`` and ``ServingFrontDoor`` by keyword, and the readers of
``smallthinker-serve-mixed-lengths`` and ``dots3-serve-long-docs`` read
series and scopes by name."""

import ast
import inspect
import os

import jax
import numpy as np
import pytest

from test_latent_lm import Toy
from test_sparse_latent_lm import Toy as SparseToy
from test_window_gqa_lm import Toy as WindowToy, _series
from znicz_tpu.core import prng
from znicz_tpu.services import engine
from znicz_tpu.services.frontdoor import ServingFrontDoor
from znicz_tpu.workflow.transformer import init_lm_params

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BUILDERS = (
    "benchmarks/drivers/serve_open_loop.py",
    "benchmarks/drivers/serve_latent_moe.py",
    "benchmarks/drivers/serve_window_moe.py",
    "benchmarks/drivers/serve_sparse_latent.py",
    "chip_smoke.py",
)


def _classic_engine():
    prng.seed_all(27)
    params = init_lm_params(17, 32, 2, 4, max_seq=64)
    return engine.PagedDecodeEngine(
        params, n_heads=4, eos_id=14, batch_size=2, block_size=8
    )


def _calls_of_one_served_request(eng, monkeypatch):
    """Serve one request; returns {program name: (argument shapes, keyword
    arguments, what it returned)} of the last call of each program."""
    seen = {}
    for name in ("_paged_decode_chunk", "_paged_prefill_prog"):
        real = getattr(engine, name)

        def spy(*args, _real=real, _name=name, **kwargs):
            shapes = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), args
            )
            out = _real(*args, **kwargs)
            seen[_name] = (shapes, kwargs, out)
            return out

        spy._cache_size = real._cache_size
        monkeypatch.setattr(engine, name, spy)
    eng.submit(np.arange(1, 12, dtype=np.int32), 6)
    eng.run()
    return seen


def test_the_traced_programs_keep_their_names(monkeypatch):
    real = {
        name: getattr(engine, name)
        for name in ("_paged_decode_chunk", "_paged_prefill_prog")
    }
    seen = _calls_of_one_served_request(_classic_engine(), monkeypatch)
    for name, fn in real.items():
        shapes, kwargs, _ = seen[name]
        assert f"@jit_{name}" in fn.lower(*shapes, **kwargs).as_text()


TOWERS = {
    "classic": _classic_engine, "latent": lambda: Toy().engine(),
    "window": lambda: WindowToy().engine(),
    "sparse": lambda: SparseToy().engine(),
}


@pytest.mark.parametrize(
    "tower, values",
    [("classic", 7), ("latent", 8), ("window", 8), ("sparse", 8)],
)
def test_the_decode_chunk_returns_what_the_benchmark_unpacks(
    tower, values, monkeypatch
):
    assert callable(engine._paged_decode_chunk._cache_size)
    eng = TOWERS[tower]()
    seen = _calls_of_one_served_request(eng, monkeypatch)
    assert len(seen["_paged_decode_chunk"][2]) == values
    assert "paged_chunk_jit_entries" in eng.compile_stats()


def test_the_builders_keywords_are_accepted():
    accepts = {
        "PagedDecodeEngine": inspect.signature(engine.PagedDecodeEngine),
        "ServingFrontDoor": inspect.signature(ServingFrontDoor),
    }
    for path in BUILDERS:
        with open(os.path.join(REPO, path)) as f:
            tree = ast.parse(f.read())
        built = [
            node for node in ast.walk(tree)
            if isinstance(node, ast.Call)
            and getattr(node.func, "id", None) in accepts
        ]
        assert {node.func.id for node in built} == set(accepts), path
        for node in built:
            accepts[node.func.id].bind(
                *node.args, **{kw.arg: None for kw in node.keywords}
            )


def test_the_window_towers_series_and_scopes_keep_their_names(monkeypatch):
    """What ``benchmarks/layer_metrics/{attn.*,moe.reglu*,moe.experts_hit*,
    cache.*,engine.preemptions*}.py`` and ``harness/smallthinker_readers.py``
    read: the registry series by name and label, and the scopes inside the
    decode program."""
    real = engine._paged_decode_chunk
    seen = _calls_of_one_served_request(WindowToy().engine(), monkeypatch)
    shapes, kwargs, _ = seen["_paged_decode_chunk"]
    text = real.lower(*shapes, **kwargs).as_text(debug_info=True)
    for scope in ("attn_window", "attn_global", "moe_dispatch", "moe_experts"):
        assert f"/{scope}/" in text, scope
    for kind in ("global", "window"):
        assert _series("znicz_serve_decode_cached_rows_total", kind=kind)
        assert _series("znicz_serve_pool_blocks_in_use", kind=kind)
    for phase in ("prefill", "decode"):
        for name in ("pairs", "busiest_pairs", "idle_experts", "layer_steps"):
            assert _series(f"znicz_serve_moe_{name}_total", phase=phase), name
    for name in (
        "znicz_serve_window_blocks_released_total",
        "znicz_serve_cache_bytes_per_resident_token",
        "znicz_serve_preemptions_total", "znicz_serve_requests_admitted_total",
        "znicz_serve_decode_steps_total",
        "znicz_serve_decode_gathered_tokens_total",
    ):
        assert _series(name), name


def test_the_selecting_towers_series_and_scopes_keep_their_names(monkeypatch):
    """What ``benchmarks/layer_metrics/{dsa.*,mla.window*}.py`` and
    ``harness/dots3_readers.py`` read: the registry series by name and
    label, and the scopes inside BOTH programs."""
    real = {
        name: getattr(engine, name)
        for name in ("_paged_decode_chunk", "_paged_prefill_prog")
    }
    seen = _calls_of_one_served_request(SparseToy().engine(), monkeypatch)
    for name, fn in real.items():
        shapes, kwargs, _ = seen[name]
        text = fn.lower(*shapes, **kwargs).as_text(debug_info=True)
        assert f"@jit_{name}" in text
        for scope in (
            "dsa_indexer", "dsa_select", "mla_sparse", "mla_window",
            "moe_dispatch", "moe_experts",
        ):
            assert f"/{scope}/" in text, (name, scope)
    for phase in ("prefill", "decode"):
        for name in ("scored", "selected"):
            assert _series(f"znicz_serve_sparse_keys_{name}_total", phase=phase)
        assert _series("znicz_serve_moe_pairs_total", phase=phase)
    for kind in ("global", "window"):
        assert _series("znicz_serve_decode_cached_rows_total", kind=kind)
        assert _series("znicz_serve_pool_blocks_in_use", kind=kind)
