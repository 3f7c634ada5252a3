"""Per-rule unit tests for the znicz-check static analyzer.

Each rule gets positive (fires) and negative (stays quiet) cases on
small inline modules; plus pragma suppression and baseline round-trip
semantics.  Pure-AST — no jax tracing happens here.
"""

import textwrap

import pytest

from znicz_tpu.analysis import engine
from znicz_tpu.analysis.rules import RULES, get_rules
from znicz_tpu.analysis.rules.sharding_axes import (
    ShardingAxisRule,
    declared_axes,
)


def run(src, rule_id, path="pkg/mod.py"):
    src = textwrap.dedent(src)
    if rule_id == "ZNC003":
        rules = [ShardingAxisRule(axes={"data", "model", "pipe"})]
    else:
        rules = [RULES[rule_id]()]
    return engine.analyze_source(src, path, rules)


def ids(findings):
    return [f.rule for f in findings]


# -- ZNC001: traced branch ----------------------------------------------


class TestTracedBranch:
    def test_if_on_traced_arg_fires(self):
        fs = run(
            """
            import jax

            @jax.jit
            def f(x):
                if x > 0:
                    return x
                return -x
            """,
            "ZNC001",
        )
        assert ids(fs) == ["ZNC001"]
        assert "x" in fs[0].message

    def test_while_on_traced_arg_fires(self):
        fs = run(
            """
            import jax

            @jax.jit
            def f(x):
                while x > 0:
                    x = x - 1
                return x
            """,
            "ZNC001",
        )
        assert ids(fs) == ["ZNC001"]

    def test_static_argname_is_exempt(self):
        fs = run(
            """
            from functools import partial
            import jax

            @partial(jax.jit, static_argnames=("greedy",))
            def f(x, greedy):
                if greedy:
                    return x
                return -x
            """,
            "ZNC001",
        )
        assert fs == []

    def test_static_argnums_is_exempt(self):
        fs = run(
            """
            from functools import partial
            import jax

            @partial(jax.jit, static_argnums=(1,))
            def f(x, n):
                if n:
                    return x
                return -x
            """,
            "ZNC001",
        )
        assert fs == []

    def test_is_none_and_shape_checks_are_exempt(self):
        fs = run(
            """
            import jax

            @jax.jit
            def f(x, mask):
                if mask is None:
                    return x
                if x.ndim == 2:
                    return x + mask
                return x
            """,
            "ZNC001",
        )
        assert fs == []

    def test_scan_body_branching_on_carry_fires(self):
        fs = run(
            """
            import jax

            def outer(xs):
                def body(carry, x):
                    if carry > 0:
                        carry = carry + x
                    return carry, x
                return jax.lax.scan(body, 0.0, xs)
            """,
            "ZNC001",
        )
        assert ids(fs) == ["ZNC001"]

    def test_call_form_jit_fires(self):
        fs = run(
            """
            import jax

            def step(x):
                if x > 0:
                    return x
                return -x

            fast = jax.jit(step)
            """,
            "ZNC001",
        )
        assert ids(fs) == ["ZNC001"]

    def test_partial_bound_kwargs_are_static(self):
        """Names bound by partial() are trace-time constants —
        branching on them is fine (pipeline.py's shard_map body does
        exactly this with n_micro/n_stages)."""
        fs = run(
            """
            from functools import partial
            import jax

            def outer(mesh, spec, x):
                def local(xs, n_micro, n_stages):
                    if n_micro < n_stages:
                        raise AssertionError("bad config")
                    return xs
                return jax.shard_map(
                    partial(local, n_micro=4, n_stages=2),
                    mesh=mesh, in_specs=(spec,), out_specs=spec,
                )(x)
            """,
            "ZNC001",
        )
        assert fs == []

    def test_builtin_map_is_not_lax_map(self):
        """Python's map() over a side-effecting helper is host code."""
        fs = run(
            """
            import os

            def f(x):
                print(x)
                return os.path.basename(x)

            def collect(items):
                return list(map(f, items))
            """,
            "ZNC002",
        )
        assert fs == []

    def test_sibling_same_named_def_is_not_conflated(self):
        """A host-side helper that merely SHARES a name with a scan
        body in another function must not be marked traced."""
        fs = run(
            """
            import jax

            def trainer(xs):
                def body(c, x):
                    return c + x, x
                return jax.lax.scan(body, 0.0, xs)

            def reporter(rows):
                def body(row):
                    if row:
                        print(row)
                for r in rows:
                    body(r)
            """,
            "ZNC002",
        )
        assert fs == []

    def test_plain_function_is_quiet(self):
        fs = run(
            """
            def f(x):
                if x > 0:
                    return x
                return -x
            """,
            "ZNC001",
        )
        assert fs == []

    def test_closure_sees_enclosing_traced_params(self):
        fs = run(
            """
            import jax

            @jax.jit
            def f(x):
                def g():
                    if x > 0:
                        return x
                    return -x
                return g()
            """,
            "ZNC001",
        )
        assert ids(fs) == ["ZNC001"]


# -- ZNC002: host effects ------------------------------------------------


class TestHostEffects:
    def test_print_in_jit_fires(self):
        fs = run(
            """
            import jax

            @jax.jit
            def f(x):
                print(x)
                return x
            """,
            "ZNC002",
        )
        assert ids(fs) == ["ZNC002"]

    def test_time_in_scan_body_fires(self):
        fs = run(
            """
            import time
            import jax

            def outer(xs):
                def body(c, x):
                    t = time.time()
                    return c + x, t
                return jax.lax.scan(body, 0.0, xs)
            """,
            "ZNC002",
        )
        assert ids(fs) == ["ZNC002"]

    def test_numpy_alias_in_grad_fires(self):
        fs = run(
            """
            import numpy as np
            import jax

            def loss(w, x):
                return np.sum(w * x)

            g = jax.grad(loss)
            """,
            "ZNC002",
        )
        assert ids(fs) == ["ZNC002"]
        assert "numpy.sum" in fs[0].message

    def test_jnp_is_quiet(self):
        fs = run(
            """
            import jax
            import jax.numpy as jnp

            @jax.jit
            def f(x):
                return jnp.sum(x)
            """,
            "ZNC002",
        )
        assert fs == []

    def test_host_code_print_is_quiet(self):
        fs = run(
            """
            def f(x):
                print(x)
                return x
            """,
            "ZNC002",
        )
        assert fs == []

    def test_device_get_and_block_until_ready_in_jit_fire(self):
        """Host syncs inside jitted code are ZNC002's jurisdiction
        (ZNC007 deliberately defers traced code to it)."""
        fs = run(
            """
            import jax

            @jax.jit
            def step(xs):
                for x in xs:
                    jax.device_get(x)
                    x.block_until_ready()
                return xs
            """,
            "ZNC002",
        )
        assert ids(fs) == ["ZNC002", "ZNC002"]

    def test_jax_shard_map_body_is_traced(self):
        """``jax.shard_map`` must count as a transform — the shard_map
        bodies are exactly the per-device code these rules exist to
        protect."""
        fs = run(
            """
            import time
            import jax

            def outer(mesh, spec, x):
                def local(xs):
                    time.time()
                    return xs
                return jax.shard_map(
                    local, mesh=mesh, in_specs=(spec,), out_specs=spec
                )(x)
            """,
            "ZNC002",
        )
        assert ids(fs) == ["ZNC002"]

    def test_partial_wrapped_shard_map_body_is_traced(self):
        """``shard_map(partial(local, ...))`` — the repo's dominant way
        of handing configured bodies to transforms."""
        fs = run(
            """
            import time
            from functools import partial
            import jax

            def outer(mesh, spec, x):
                def local(xs, scale):
                    time.time()
                    return xs * scale
                return jax.shard_map(
                    partial(local, scale=2.0),
                    mesh=mesh, in_specs=(spec,), out_specs=spec,
                )(x)
            """,
            "ZNC002",
        )
        assert ids(fs) == ["ZNC002"]

    def test_experimental_shard_map_spelling_is_traced(self):
        fs = run(
            """
            import time
            from jax.experimental.shard_map import shard_map

            def outer(mesh, spec, x):
                def local(xs):
                    time.time()
                    return xs
                return shard_map(
                    local, mesh=mesh, in_specs=(spec,), out_specs=spec
                )(x)
            """,
            "ZNC002",
        )
        assert ids(fs) == ["ZNC002"]


# -- ZNC003: sharding axes -----------------------------------------------


class TestShardingAxes:
    def test_unknown_axis_in_partition_spec_fires(self):
        fs = run(
            """
            from jax.sharding import PartitionSpec as P

            spec = P("batch", None)
            """,
            "ZNC003",
        )
        assert ids(fs) == ["ZNC003"]
        assert "batch" in fs[0].message

    def test_known_axes_are_quiet(self):
        fs = run(
            """
            from jax.sharding import PartitionSpec as P

            a = P("data", None)
            b = P(("data", "model"))
            c = P(None, "pipe")
            """,
            "ZNC003",
        )
        assert fs == []

    def test_unknown_axis_in_collective_kwarg_fires(self):
        fs = run(
            """
            import jax

            def f(x):
                return jax.lax.psum(x, axis_name="dp")
            """,
            "ZNC003",
        )
        assert ids(fs) == ["ZNC003"]

    def test_unknown_axis_in_positional_collective_arg_fires(self):
        """psum(x, "bacth") — the dominant positional convention."""
        fs = run(
            """
            import jax

            def f(x):
                return jax.lax.psum(x, "bacth")
            """,
            "ZNC003",
        )
        assert ids(fs) == ["ZNC003"]

    def test_non_jax_method_named_like_a_collective_is_quiet(self):
        """`client.all_gather("metrics")` is someone's own method, not a
        jax collective — its string args are not axis names."""
        fs = run(
            """
            def push(client, mesh_like):
                client.all_gather("metrics")
                client.psum("totals")
                mesh_like.Mesh(None, ("rows", "cols"))
            """,
            "ZNC003",
        )
        assert fs == []

    def test_known_positional_collective_axis_is_quiet(self):
        fs = run(
            """
            import jax

            def f(x):
                return jax.lax.psum(x, "data")
            """,
            "ZNC003",
        )
        assert fs == []

    def test_mesh_axis_names_checked(self):
        fs = run(
            """
            from jax.sharding import Mesh

            def build(grid):
                return Mesh(grid, ("rows", "cols"))
            """,
            "ZNC003",
        )
        assert sorted(f.message.split("'")[1] for f in fs) == [
            "cols",
            "rows",
        ]

    def test_declared_axes_parses_real_mesh_module(self):
        axes = declared_axes()
        assert {"data", "model", "pipe"} <= axes

    def test_axes_resolved_against_analyzed_root(self, tmp_path):
        """A different tree's mesh.py governs that tree's analysis —
        e.g. a worktree branch that legitimately adds an axis."""
        mesh_dir = tmp_path / "znicz_tpu" / "parallel"
        mesh_dir.mkdir(parents=True)
        (mesh_dir / "mesh.py").write_text('EXPERT_AXIS = "expert"\n')
        mod = tmp_path / "mod.py"
        mod.write_text(
            "from jax.sharding import PartitionSpec as P\n"
            'a = P("expert")\n'
            'b = P("bogus")\n'
        )
        fs = engine.analyze_paths(
            [str(mod)],
            root=str(tmp_path),
            rules=[ShardingAxisRule()],
        )
        assert [f.rule for f in fs] == ["ZNC003"]
        assert "bogus" in fs[0].message and "expert" in fs[0].message


# -- ZNC004: prng keys ---------------------------------------------------


class TestPrngKeys:
    def test_hardcoded_key_fires(self):
        fs = run(
            """
            import jax

            k = jax.random.key(0)
            """,
            "ZNC004",
        )
        assert ids(fs) == ["ZNC004"]

    def test_hardcoded_prngkey_fires(self):
        fs = run(
            """
            import jax

            k = jax.random.PRNGKey(42)
            """,
            "ZNC004",
        )
        assert ids(fs) == ["ZNC004"]

    def test_core_prng_is_sanctioned(self):
        fs = run(
            """
            import jax

            k = jax.random.key(0)
            """,
            "ZNC004",
            path="znicz_tpu/core/prng.py",
        )
        assert fs == []

    def test_key_reuse_fires_once_per_extra_use(self):
        fs = run(
            """
            import jax

            def f(key, shape):
                a = jax.random.normal(key, shape)
                b = jax.random.uniform(key, shape)
                return a + b
            """,
            "ZNC004",
        )
        assert ids(fs) == ["ZNC004"]
        assert "key" in fs[0].message

    def test_split_keys_are_quiet(self):
        fs = run(
            """
            import jax

            def f(key, shape):
                k1, k2 = jax.random.split(key)
                a = jax.random.normal(k1, shape)
                b = jax.random.uniform(k2, shape)
                return a + b
            """,
            "ZNC004",
        )
        assert fs == []

    def test_rebound_key_is_skipped(self):
        fs = run(
            """
            import jax

            def f(key, shape):
                a = jax.random.normal(key, shape)
                key = jax.random.split(key, 1)[0]
                b = jax.random.uniform(key, shape)
                return a + b
            """,
            "ZNC004",
        )
        assert fs == []

    def test_sibling_closures_with_own_key_params_are_quiet(self):
        """Nested scopes must not be conflated: two closures each with
        their OWN `key` parameter is not reuse."""
        fs = run(
            """
            import jax

            def outer(shape):
                def f(key):
                    return jax.random.uniform(key, shape)

                def g(key):
                    return jax.random.normal(key, shape)

                return f, g
            """,
            "ZNC004",
        )
        assert fs == []

    def test_reuse_inside_nested_def_reported_exactly_once(self):
        fs = run(
            """
            import jax

            def outer(shape):
                def f(key):
                    a = jax.random.uniform(key, shape)
                    b = jax.random.normal(key, shape)
                    return a + b

                return f
            """,
            "ZNC004",
        )
        assert ids(fs) == ["ZNC004"]

    def test_branch_exclusive_consumption_is_quiet(self):
        """if/else arms are mutually exclusive — only one sampler ever
        consumes the key."""
        fs = run(
            """
            import jax

            def f(key, shape, gaussian):
                if gaussian:
                    x = jax.random.normal(key, shape)
                else:
                    x = jax.random.uniform(key, shape)
                return x
            """,
            "ZNC004",
        )
        assert fs == []

    def test_keyword_spelled_key_reuse_fires(self):
        fs = run(
            """
            import jax

            def f(key, shape):
                a = jax.random.normal(key=key, shape=shape)
                b = jax.random.uniform(key=key, shape=shape)
                return a + b
            """,
            "ZNC004",
        )
        assert ids(fs) == ["ZNC004"]

    def test_keyword_spelled_hardcoded_seed_fires(self):
        fs = run(
            """
            import jax

            k = jax.random.PRNGKey(seed=7)
            """,
            "ZNC004",
        )
        assert ids(fs) == ["ZNC004"]

    def test_lambda_key_reuse_fires(self):
        fs = run(
            """
            import jax

            sample = lambda k, s: (
                jax.random.normal(k, s) + jax.random.uniform(k, s)
            )
            """,
            "ZNC004",
        )
        assert ids(fs) == ["ZNC004"]

    def test_module_level_key_reuse_fires(self):
        fs = run(
            """
            import jax

            key = jax.random.split(SEED)[0]
            a = jax.random.normal(key, (3,))
            b = jax.random.uniform(key, (3,))
            """,
            "ZNC004",
        )
        assert ids(fs) == ["ZNC004"]

    def test_locally_bound_key_reuse_fires(self):
        """The defining assignment must not mask later reuse — the
        classic `key = ...; use; use` silent-correlation bug."""
        fs = run(
            """
            import jax

            def f(seed, shape):
                key = jax.random.fold_in(jax.random.split(seed)[0], 1)
                a = jax.random.normal(key, shape)
                b = jax.random.uniform(key, shape)
                return a + b
            """,
            "ZNC004",
        )
        assert ids(fs) == ["ZNC004"]


# -- ZNC005: donation ----------------------------------------------------


class TestDonation:
    def test_jit_call_without_donation_fires(self):
        fs = run(
            """
            import jax

            def step(state, x):
                return state, x

            fast = jax.jit(step)
            """,
            "ZNC005",
        )
        assert ids(fs) == ["ZNC005"]
        assert "state" in fs[0].message

    def test_decorated_without_donation_fires(self):
        fs = run(
            """
            import jax

            @jax.jit
            def step(state, x):
                return state, x
            """,
            "ZNC005",
        )
        assert ids(fs) == ["ZNC005"]

    def test_donate_argnums_is_quiet(self):
        fs = run(
            """
            import jax

            def step(state, x):
                return state, x

            fast = jax.jit(step, donate_argnums=(0,))
            """,
            "ZNC005",
        )
        assert fs == []

    def test_no_state_param_is_quiet(self):
        fs = run(
            """
            import jax

            @jax.jit
            def f(x, y):
                return x + y
            """,
            "ZNC005",
        )
        assert fs == []

    def test_trainstate_annotation_fires_despite_renamed_param(self):
        # a renamed state arg with a TrainState annotation still gets
        # the donation check (the name heuristic alone would miss it)
        fs = run(
            """
            import jax
            from znicz_tpu.nn.train_state import TrainState

            @jax.jit
            def step(ts: TrainState, x):
                return ts, x
            """,
            "ZNC005",
        )
        assert ids(fs) == ["ZNC005"]
        assert "ts" in fs[0].message

    def test_dotted_and_optional_annotations_fire(self):
        fs = run(
            """
            import jax
            from typing import Optional
            from znicz_tpu.nn import train_state

            @jax.jit
            def a(s0: train_state.TrainState, x):
                return s0, x

            @jax.jit
            def b(maybe: Optional[TrainState], x):
                return maybe, x
            """,
            "ZNC005",
        )
        assert ids(fs) == ["ZNC005", "ZNC005"]

    def test_string_forward_reference_annotation_fires(self):
        fs = run(
            """
            import jax

            @jax.jit
            def step(ts: "TrainState", x):
                return ts, x
            """,
            "ZNC005",
        )
        assert ids(fs) == ["ZNC005"]

    def test_lookalike_type_name_is_quiet(self):
        # word-boundary matching: TrainStateless is a different type
        fs = run(
            """
            import jax

            @jax.jit
            def step(ts: "TrainStateless", x):
                return ts, x
            """,
            "ZNC005",
        )
        assert fs == []

    def test_annotated_with_donation_is_quiet(self):
        fs = run(
            """
            import jax

            def step(ts: TrainState, x):
                return ts, x

            fast = jax.jit(step, donate_argnums=(0,))
            """,
            "ZNC005",
        )
        assert fs == []

    def test_annotated_static_param_is_quiet(self):
        fs = run(
            """
            import jax
            from functools import partial

            @partial(jax.jit, static_argnames=("ts",))
            def step(ts: TrainState, x):
                return ts, x
            """,
            "ZNC005",
        )
        assert fs == []


# -- ZNC006: mutable state -----------------------------------------------


class TestMutableState:
    def test_mutable_default_fires(self):
        fs = run(
            """
            def f(x, acc=[]):
                acc.append(x)
                return acc
            """,
            "ZNC006",
        )
        assert ids(fs) == ["ZNC006"]

    def test_none_default_is_quiet(self):
        fs = run(
            """
            def f(x, acc=None):
                return acc
            """,
            "ZNC006",
        )
        assert fs == []

    def test_empty_tuple_default_is_quiet(self):
        fs = run(
            """
            def f(x, shape=()):
                return shape
            """,
            "ZNC006",
        )
        assert fs == []

    def test_module_mutable_captured_by_jit_fires(self):
        fs = run(
            """
            import jax

            CACHE = {}

            @jax.jit
            def f(x):
                return x * CACHE["scale"]
            """,
            "ZNC006",
        )
        assert ids(fs) == ["ZNC006"]

    def test_module_mutable_in_host_code_is_quiet(self):
        fs = run(
            """
            CACHE = {}

            def f(x):
                return CACHE.get(x)
            """,
            "ZNC006",
        )
        assert fs == []

    def test_local_rebinding_of_module_name_is_quiet(self):
        """A name assigned inside the function is local THROUGHOUT it
        (python scoping) — no module-level capture happens."""
        fs = run(
            """
            import jax

            CACHE = []

            @jax.jit
            def f(x):
                CACHE = [x]
                return CACHE[0]
            """,
            "ZNC006",
        )
        assert fs == []

    def test_global_in_jit_fires(self):
        fs = run(
            """
            import jax

            counter = 0

            @jax.jit
            def f(x):
                global counter
                counter = counter + 1
                return x
            """,
            "ZNC006",
        )
        assert "ZNC006" in ids(fs)


# -- ZNC007: host sync in loop -------------------------------------------


class TestHostSync:
    def test_device_get_in_loop_fires(self):
        fs = run(
            """
            import jax

            def epoch(batches):
                out = []
                for b in batches:
                    out.append(jax.device_get(b))
                return out
            """,
            "ZNC007",
        )
        assert ids(fs) == ["ZNC007"]

    def test_block_until_ready_in_loop_fires(self):
        fs = run(
            """
            def epoch(xs):
                for x in xs:
                    x.block_until_ready()
            """,
            "ZNC007",
        )
        assert ids(fs) == ["ZNC007"]

    def test_time_time_in_while_fires(self):
        fs = run(
            """
            import time

            def run():
                while True:
                    t = time.time()
                    if t > 10:
                        break
            """,
            "ZNC007",
        )
        assert ids(fs) == ["ZNC007"]

    def test_outside_loop_is_quiet(self):
        fs = run(
            """
            import jax
            import time

            def finish(acc):
                t = time.time()
                return jax.device_get(acc), t
            """,
            "ZNC007",
        )
        assert fs == []

    def test_closure_defined_in_loop_is_quiet(self):
        fs = run(
            """
            import jax

            def make(xs):
                fns = []
                for x in xs:
                    def fetch():
                        return jax.device_get(x)
                    fns.append(fetch)
                return fns
            """,
            "ZNC007",
        )
        assert fs == []


# -- ZNC008: swallowed exceptions ----------------------------------------


class TestSwallowedExceptions:
    def test_bare_except_fires(self):
        fs = run(
            """
            def f():
                try:
                    return 1
                except:
                    return 0
            """,
            "ZNC008",
        )
        assert ids(fs) == ["ZNC008"]
        assert "bare" in fs[0].message

    def test_silent_pass_fires(self):
        fs = run(
            """
            def f():
                try:
                    return 1
                except Exception:
                    pass
            """,
            "ZNC008",
        )
        assert ids(fs) == ["ZNC008"]

    def test_logging_handler_is_quiet(self):
        fs = run(
            """
            import logging

            def f():
                try:
                    return 1
                except Exception:
                    logging.exception("boom")
                    return 0
            """,
            "ZNC008",
        )
        assert fs == []

    def test_return_fallback_is_quiet(self):
        """``return <fallback>`` is a documented degraded result, not a
        swallowed exception."""
        fs = run(
            """
            def f():
                try:
                    return compute()
                except OSError:
                    return []
            """,
            "ZNC008",
        )
        assert fs == []

    def test_bare_return_fires(self):
        fs = run(
            """
            def f():
                try:
                    work()
                except OSError:
                    return
            """,
            "ZNC008",
        )
        assert ids(fs) == ["ZNC008"]

    def test_reraise_is_quiet(self):
        fs = run(
            """
            def f():
                try:
                    return 1
                except Exception as e:
                    raise RuntimeError("ctx") from e
            """,
            "ZNC008",
        )
        assert fs == []


# -- ZNC009: wall-clock durations ----------------------------------------


class TestWallClockDuration:
    def test_direct_subtraction_fires(self):
        fs = run(
            """
            import time

            def f(t0):
                return time.time() - t0
            """,
            "ZNC009",
        )
        assert ids(fs) == ["ZNC009"]

    def test_reversed_direct_subtraction_fires(self):
        fs = run(
            """
            import time

            def remaining(deadline):
                return deadline - time.time()
            """,
            "ZNC009",
        )
        assert ids(fs) == ["ZNC009"]

    def test_variable_pair_fires(self):
        fs = run(
            """
            import time

            def f(work):
                t0 = time.time()
                work()
                t1 = time.time()
                return t1 - t0
            """,
            "ZNC009",
        )
        assert ids(fs) == ["ZNC009"]

    def test_attribute_pair_fires(self):
        fs = run(
            """
            import time

            class Watch:
                def start(self):
                    self._t0 = time.time()

                def lap(self):
                    self._t1 = time.time()
                    return self._t1 - self._t0
            """,
            "ZNC009",
        )
        assert ids(fs) == ["ZNC009"]

    def test_from_import_alias_fires(self):
        fs = run(
            """
            from time import time

            def f(t0):
                return time() - t0
            """,
            "ZNC009",
        )
        assert ids(fs) == ["ZNC009"]

    def test_timestamp_use_is_quiet(self):
        fs = run(
            """
            import time

            def stamp(record):
                record["created_at"] = time.time()
                return record
            """,
            "ZNC009",
        )
        assert fs == []

    def test_monotonic_and_perf_counter_quiet(self):
        fs = run(
            """
            import time

            def f(work):
                t0 = time.monotonic()
                p0 = time.perf_counter()
                work()
                return time.monotonic() - t0, time.perf_counter() - p0
            """,
            "ZNC009",
        )
        assert fs == []

    def test_unrelated_names_quiet(self):
        # a subtraction of two NON-wall names in a module that also
        # calls time.time() elsewhere must not fire
        fs = run(
            """
            import time

            NOW = time.time()

            def f(a, b):
                return a - b
            """,
            "ZNC009",
        )
        assert fs == []

    def test_pragma_exempts(self):
        fs = run(
            """
            import time

            def age(mtime):
                # cross-process file age IS an epoch difference
                return time.time() - mtime  # znicz-check: disable=ZNC009
            """,
            "ZNC009",
        )
        assert fs == []


# -- ZNC010: unbounded blocking in services/ ------------------------------


SERVICES_PATH = "znicz_tpu/services/mod.py"


class TestUnboundedBlocking:
    def test_queue_get_without_timeout_fires(self):
        fs = run(
            """
            import queue

            def pull(q):
                return q.get()
            """,
            "ZNC010",
            path=SERVICES_PATH,
        )
        assert ids(fs) == ["ZNC010"]
        assert "timeout" in fs[0].message

    def test_event_wait_and_thread_join_and_acquire_fire(self):
        fs = run(
            """
            def sync(evt, thread, lock):
                evt.wait()
                thread.join()
                lock.acquire()
            """,
            "ZNC010",
            path=SERVICES_PATH,
        )
        assert ids(fs) == ["ZNC010"] * 3

    def test_bounded_calls_are_quiet(self):
        fs = run(
            """
            def sync(q, evt, thread, lock, grace):
                q.get(timeout=1.0)
                q.get_nowait()
                evt.wait(timeout=grace)
                thread.join(grace)
                lock.acquire(timeout=0.5)
                lock.acquire(False)
                lock.acquire(blocking=False)
                q.get(block=False)
            """,
            "ZNC010",
            path=SERVICES_PATH,
        )
        assert fs == []

    def test_non_blocking_homonyms_are_quiet(self):
        # str.join / dict.get / sound-alike methods with args must not
        # be confused with synchronization primitives
        fs = run(
            """
            def fmt(parts, d, k):
                return ", ".join(parts) + str(d.get(k))
            """,
            "ZNC010",
            path=SERVICES_PATH,
        )
        assert fs == []

    def test_module_level_wait_is_quiet(self):
        fs = run(
            """
            import os

            def reap():
                return os.wait()
            """,
            "ZNC010",
            path=SERVICES_PATH,
        )
        assert fs == []

    def test_outside_services_is_quiet(self):
        fs = run(
            """
            def pull(q):
                return q.get()
            """,
            "ZNC010",
            path="znicz_tpu/loader/prefetch.py",
        )
        assert fs == []

    def test_cluster_scope_fires(self):
        # ISSUE 8: the serving tier grew znicz_tpu/cluster/ — the
        # router/registry threads strand CLIENTS when they hang, so
        # the no-unbounded-waits contract covers them too
        fs = run(
            """
            def pull(q, evt):
                evt.wait()
                return q.get()
            """,
            "ZNC010",
            path="znicz_tpu/cluster/router.py",
        )
        assert ids(fs) == ["ZNC010"] * 2

    def test_cluster_bounded_calls_are_quiet(self):
        fs = run(
            """
            def sync(evt, thread):
                evt.wait(timeout=1.0)
                thread.join(timeout=2.0)
            """,
            "ZNC010",
            path="znicz_tpu/cluster/registry.py",
        )
        assert fs == []

    def test_pragma_exempts(self):
        fs = run(
            """
            def pull(q):
                # the producer is in-process and cannot die silently
                return q.get()  # znicz-check: disable=ZNC010
            """,
            "ZNC010",
            path=SERVICES_PATH,
        )
        assert fs == []


# -- ZNC011: dynamic metric names -----------------------------------------


class TestDynamicMetricNames:
    def test_fstring_name_fires(self):
        fs = run(
            """
            from znicz_tpu import observability

            def make(kind):
                return observability.counter(f"znicz_{kind}_total")
            """,
            "ZNC011",
        )
        assert ids(fs) == ["ZNC011"]
        assert "label" in fs[0].message

    def test_concat_percent_and_format_fire(self):
        fs = run(
            """
            def make(reg, name, phase):
                a = reg.gauge("znicz_" + name)
                b = reg.histogram("znicz_%s_seconds" % phase)
                c = reg.counter("znicz_{}_total".format(name))
                return a, b, c
            """,
            "ZNC011",
        )
        assert ids(fs) == ["ZNC011"] * 3

    def test_bare_and_keyword_name_forms_fire(self):
        fs = run(
            """
            from znicz_tpu.observability import counter, gauge

            def make(kind):
                counter(f"znicz_{kind}_total")
                gauge(name="znicz_" + kind)
            """,
            "ZNC011",
        )
        assert ids(fs) == ["ZNC011"] * 2

    def test_static_names_and_variables_stay_quiet(self):
        # literal names, a pass-through variable (PhaseTimer's metric
        # param), labels carrying the varying value, and non-factory
        # homonyms must all stay quiet
        fs = run(
            """
            from collections import Counter

            def make(reg, metric, kind):
                a = reg.counter("znicz_serve_requests_total", "h",
                                ("kind",))
                a.labels(kind=kind).inc()
                b = reg.histogram(metric)  # variable: may be static
                c = Counter(f"not a {kind} metric")  # uppercase: not ours
                d = "x".format()  # format off a factory-free call
                return a, b, c, d
            """,
            "ZNC011",
        )
        assert fs == []

    def test_nested_concat_with_literal_fires(self):
        fs = run(
            """
            def make(reg, a, b):
                return reg.counter(a + b + "_total")
            """,
            "ZNC011",
        )
        assert ids(fs) == ["ZNC011"]

    def test_plain_fstring_without_interpolation_is_quiet(self):
        fs = run(
            """
            def make(reg):
                return reg.counter(f"znicz_static_total")
            """,
            "ZNC011",
        )
        assert fs == []

    def test_pragma_exempts(self):
        fs = run(
            """
            def make(reg, kind):
                # one-off migration shim, bounded set of kinds
                return reg.counter(f"znicz_{kind}_total")  # znicz-check: disable=ZNC011
            """,
            "ZNC011",
        )
        assert fs == []


# -- ZNC012: lock discipline ----------------------------------------------


class TestLockDiscipline:
    RACY = """
        import threading

        class Door:
            def __init__(self):
                self._lock = threading.Lock()
                self._pending = []
                self._thread = threading.Thread(
                    target=self._loop, daemon=True
                )

            def submit(self, item):
                with self._lock:
                    self._pending.append(item)

            def _loop(self):
                while True:
                    expired = [x for x in list(self._pending) if x]
        """

    def test_bare_iterate_of_locked_container_fires(self):
        fs = run(self.RACY, "ZNC012", path=SERVICES_PATH)
        assert ids(fs) == ["ZNC012"]
        assert "_pending" in fs[0].message
        assert "thread:_loop" in fs[0].message

    def test_lock_correct_equivalent_is_quiet(self):
        fs = run(
            """
            import threading

            class Door:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._pending = []
                    self._thread = threading.Thread(
                        target=self._loop, daemon=True
                    )

                def submit(self, item):
                    with self._lock:
                        self._pending.append(item)

                def _loop(self):
                    while True:
                        with self._lock:
                            expired = list(self._pending)
            """,
            "ZNC012",
            path=SERVICES_PATH,
        )
        assert fs == []

    def test_bare_write_to_lock_read_flag_fires(self):
        # the shipped shape: a flag READ under the lock on the client
        # path, STORED bare from two different threads
        fs = run(
            """
            import threading

            class Door:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._closed = False
                    self._thread = threading.Thread(
                        target=self._loop, daemon=True
                    )

                def submit(self):
                    with self._lock:
                        if self._closed:
                            raise RuntimeError("closed")

                def close(self):
                    self._closed = True

                def _loop(self):
                    self._closed = True
            """,
            "ZNC012",
            path=SERVICES_PATH,
        )
        assert ids(fs) == ["ZNC012", "ZNC012"]

    def test_plain_read_of_atomic_is_quiet(self):
        # reading a lock-guarded counter without the lock is stale,
        # not torn — the negative case the issue pins
        fs = run(
            """
            import threading

            class Door:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._n = 0
                    self._thread = threading.Thread(
                        target=self._loop, daemon=True
                    )

                def _loop(self):
                    with self._lock:
                        self._n += 1

                def stats(self):
                    return {"n": self._n, "big": self._n > 10}
            """,
            "ZNC012",
            path=SERVICES_PATH,
        )
        assert fs == []

    def test_init_writes_are_quiet(self):
        fs = run(
            """
            import threading

            class Door:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._items = []
                    self._items.append("seed")

                def add(self, x):
                    with self._lock:
                        self._items.append(x)
            """,
            "ZNC012",
            path=SERVICES_PATH,
        )
        assert fs == []

    def test_single_thread_root_is_quiet(self):
        # an attribute only the dedicated thread ever touches cannot
        # race, locked sometimes or not
        fs = run(
            """
            import threading

            class Door:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._scratch = []
                    self._thread = threading.Thread(
                        target=self._loop, daemon=True
                    )

                def _loop(self):
                    self._a()
                    self._b()

                def _a(self):
                    with self._lock:
                        self._scratch.append(1)

                def _b(self):
                    self._scratch.append(2)
            """,
            "ZNC012",
            path=SERVICES_PATH,
        )
        assert fs == []

    def test_lock_held_by_caller_convention_is_quiet(self):
        # a private method whose every call site holds the lock runs
        # under it (the repo's documented "lock held by the caller")
        fs = run(
            """
            import threading

            class Roster:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._replicas = {}
                    self._thread = threading.Thread(
                        target=self._loop, daemon=True
                    )

                def register(self, name):
                    with self._lock:
                        self._replicas[name] = 1
                        self._update_gauges()

                def _loop(self):
                    with self._lock:
                        self._update_gauges()

                def _update_gauges(self):
                    for name in list(self._replicas):
                        pass
            """,
            "ZNC012",
            path=SERVICES_PATH,
        )
        assert fs == []

    def test_immutable_config_iteration_is_quiet(self):
        # a tuple assigned only in __init__ is config, not shared
        # mutable state — iterating it bare cannot race
        fs = run(
            """
            import threading

            class Mon:
                def __init__(self, windows):
                    self._lock = threading.Lock()
                    self.windows = tuple(windows)
                    self._ring = []
                    self._thread = threading.Thread(
                        target=self._loop, daemon=True
                    )

                def _loop(self):
                    with self._lock:
                        self._ring.append(1)

                def snapshot(self):
                    return [w for w in self.windows]
            """,
            "ZNC012",
            path=SERVICES_PATH,
        )
        assert fs == []

    def test_class_without_lock_is_quiet(self):
        fs = run(
            """
            import threading

            class Door:
                def __init__(self):
                    self._items = []
                    self._thread = threading.Thread(
                        target=self._loop, daemon=True
                    )

                def add(self, x):
                    self._items.append(x)

                def _loop(self):
                    self._items.clear()
            """,
            "ZNC012",
            path=SERVICES_PATH,
        )
        assert fs == []

    def test_outside_serving_tier_is_quiet(self):
        fs = run(self.RACY, "ZNC012", path="znicz_tpu/loader/x.py")
        assert fs == []

    def test_observability_scope_fires(self):
        fs = run(
            self.RACY, "ZNC012", path="znicz_tpu/observability/x.py"
        )
        assert ids(fs) == ["ZNC012"]

    def test_pragma_exempts(self):
        fs = run(
            """
            import threading

            class Door:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._closed = False
                    self._thread = threading.Thread(
                        target=self._loop, daemon=True
                    )

                def submit(self):
                    with self._lock:
                        return self._closed

                def _loop(self):
                    # atomic bool store; stale reads are acceptable
                    self._closed = True  # znicz-check: disable=ZNC012
            """,
            "ZNC012",
            path=SERVICES_PATH,
        )
        assert fs == []


# -- ZNC013: thread exception sink -----------------------------------------


class TestThreadExceptionSink:
    def test_unguarded_method_target_fires(self):
        fs = run(
            """
            import threading

            class Door:
                def start(self):
                    self._thread = threading.Thread(
                        target=self._loop, daemon=True
                    )
                    self._thread.start()

                def _loop(self):
                    while True:
                        self._sweep()
            """,
            "ZNC013",
            path=SERVICES_PATH,
        )
        assert ids(fs) == ["ZNC013"]
        assert "_loop" in fs[0].message

    def test_log_wrapped_loop_is_quiet(self):
        fs = run(
            """
            import logging
            import threading

            logger = logging.getLogger(__name__)

            class Door:
                def start(self):
                    self._thread = threading.Thread(
                        target=self._loop, daemon=True
                    )

                def _loop(self):
                    while not self._stop.wait(timeout=2.0):
                        try:
                            self._sweep()
                        except Exception:
                            logger.warning("sweep failed", exc_info=True)
            """,
            "ZNC013",
            path=SERVICES_PATH,
        )
        assert fs == []

    def test_narrow_handler_still_fires(self):
        fs = run(
            """
            import logging
            import threading

            logger = logging.getLogger(__name__)

            class Door:
                def start(self):
                    threading.Thread(target=self._loop).start()

                def _loop(self):
                    try:
                        self._sweep()
                    except OSError:
                        logger.warning("io failed")
            """,
            "ZNC013",
            path=SERVICES_PATH,
        )
        assert ids(fs) == ["ZNC013"]

    def test_silent_broad_handler_still_fires(self):
        # `except Exception: pass` protects nothing (and ZNC008 flags
        # the swallow separately)
        fs = run(
            """
            import threading

            class Door:
                def start(self):
                    threading.Thread(target=self._loop).start()

                def _loop(self):
                    try:
                        self._sweep()
                    except Exception:
                        pass
            """,
            "ZNC013",
            path=SERVICES_PATH,
        )
        assert ids(fs) == ["ZNC013"]

    def test_typed_event_handler_is_the_sink(self):
        # the front door's shape: the broad handler delegates to the
        # typed-failure path; the rule does not demand infinite regress
        fs = run(
            """
            import threading

            class Door:
                def start(self):
                    threading.Thread(target=self._loop).start()

                def _loop(self):
                    while True:
                        try:
                            self._tick()
                        except Exception as exc:
                            self._engine_failure(exc)
            """,
            "ZNC013",
            path=SERVICES_PATH,
        )
        assert fs == []

    def test_module_level_target_fires(self):
        fs = run(
            """
            import threading

            def worker(q):
                while True:
                    handle(q.get(timeout=1.0))

            def start(q):
                threading.Thread(target=worker, args=(q,)).start()
            """,
            "ZNC013",
            path=SERVICES_PATH,
        )
        assert ids(fs) == ["ZNC013"]
        assert "worker" in fs[0].message

    def test_lambda_target_fires(self):
        fs = run(
            """
            import threading

            def start(server):
                threading.Thread(target=lambda: server.run()).start()
            """,
            "ZNC013",
            path=SERVICES_PATH,
        )
        assert ids(fs) == ["ZNC013"]

    def test_unresolvable_target_is_skipped(self):
        fs = run(
            """
            import threading

            def start(server):
                threading.Thread(target=server.shutdown).start()
            """,
            "ZNC013",
            path=SERVICES_PATH,
        )
        assert fs == []

    def test_outside_serving_tier_is_quiet(self):
        fs = run(
            """
            import threading

            def worker():
                risky()

            threading.Thread(target=worker).start()
            """,
            "ZNC013",
            path="znicz_tpu/loader/prefetch.py",
        )
        assert fs == []

    def test_reraising_handler_is_not_a_sink(self):
        """``raise RuntimeError(exc)`` still kills the thread — the
        exception-constructor call must not count as handling."""
        fs = run(
            """
            import threading

            class Door:
                def start(self):
                    threading.Thread(target=self._loop).start()

                def _loop(self):
                    try:
                        self._work()
                    except Exception as exc:
                        raise RuntimeError(exc)
            """,
            "ZNC013",
            path=SERVICES_PATH,
        )
        assert ids(fs) == ["ZNC013"]

    def test_logging_then_reraising_handler_is_a_sink(self):
        # the death is at least a LOGGED event; the log call (outside
        # the raise) qualifies
        fs = run(
            """
            import logging
            import threading

            logger = logging.getLogger(__name__)

            class Door:
                def start(self):
                    threading.Thread(target=self._loop).start()

                def _loop(self):
                    try:
                        self._work()
                    except Exception as exc:
                        logger.exception("worker died")
                        raise
            """,
            "ZNC013",
            path=SERVICES_PATH,
        )
        assert fs == []

    def test_pragma_exempts(self):
        fs = run(
            """
            import threading

            class Pusher:
                def start(self):
                    # push_now never raises (catches all internally)
                    t = threading.Thread(  # znicz-check: disable=ZNC013
                        target=self._loop,
                    )
                    t.start()

                def _loop(self):
                    while not self._stop.wait(timeout=1.0):
                        self.push_now()
            """,
            "ZNC013",
            path=SERVICES_PATH,
        )
        assert fs == []


# -- pragmas -------------------------------------------------------------


class TestPragmas:
    SRC = """
        def f():
            try:
                return 1
            except Exception:{pragma}
                pass
        """

    def test_inline_disable(self):
        src = self.SRC.format(
            pragma="  # znicz-check: disable=ZNC008"
        )
        assert run(src, "ZNC008") == []

    def test_inline_disable_all(self):
        src = self.SRC.format(pragma="  # znicz-check: disable=all")
        assert run(src, "ZNC008") == []

    def test_inline_disable_other_rule_still_fires(self):
        src = self.SRC.format(
            pragma="  # znicz-check: disable=ZNC001"
        )
        assert ids(run(src, "ZNC008")) == ["ZNC008"]

    def test_file_level_disable(self):
        src = (
            "# znicz-check: disable-file=ZNC008\n"
            + textwrap.dedent(self.SRC.format(pragma=""))
        )
        assert engine.analyze_source(
            src, "x.py", [RULES["ZNC008"]()]
        ) == []


# -- baseline ------------------------------------------------------------


class TestBaseline:
    SRC = """
        def f():
            try:
                return 1
            except Exception:
                pass
        """

    def findings(self):
        return run(self.SRC, "ZNC008")

    def test_round_trip(self, tmp_path):
        fs = self.findings()
        path = str(tmp_path / "baseline.json")
        engine.write_baseline(fs, path)
        baseline = engine.load_baseline(path)
        assert engine.new_findings(fs, baseline) == []

    def test_new_finding_not_suppressed(self, tmp_path):
        path = str(tmp_path / "baseline.json")
        engine.write_baseline(self.findings(), path)
        src = textwrap.dedent(self.SRC) + textwrap.dedent(
            """
            def g():
                try:
                    return 2
                except ValueError:
                    pass
            """
        )
        fs = engine.analyze_source(
            src, "pkg/mod.py", [RULES["ZNC008"]()]
        )
        new = engine.new_findings(fs, engine.load_baseline(path))
        assert len(new) == 1
        assert new[0].symbol == "g"

    def test_fingerprint_survives_line_shift(self, tmp_path):
        path = str(tmp_path / "baseline.json")
        engine.write_baseline(self.findings(), path)
        shifted = "# a new comment line\n\n" + textwrap.dedent(self.SRC)
        fs = engine.analyze_source(
            shifted, "pkg/mod.py", [RULES["ZNC008"]()]
        )
        assert engine.new_findings(fs, engine.load_baseline(path)) == []

    def test_stale_entries_reported(self, tmp_path):
        path = str(tmp_path / "baseline.json")
        engine.write_baseline(self.findings(), path)
        stale = engine.stale_baseline_entries(
            [], engine.load_baseline(path)
        )
        assert sum(stale.values()) == 1

    def test_missing_baseline_is_empty(self, tmp_path):
        assert engine.load_baseline(str(tmp_path / "nope.json")) == {}


# -- engine odds and ends ------------------------------------------------


class TestEngine:
    def test_rule_catalog_has_eight_active_rules(self):
        assert len(RULES) >= 8
        assert len({cls.severity for cls in RULES.values()}) <= 2

    def test_get_rules_select_and_ignore(self):
        assert [r.id for r in get_rules(select=["ZNC001"])] == ["ZNC001"]
        assert "ZNC001" not in [
            r.id for r in get_rules(ignore=["ZNC001"])
        ]
        with pytest.raises(ValueError):
            get_rules(select=["ZNC999"])

    def test_write_baseline_refuses_partial_rule_set(self, tmp_path):
        """--write-baseline under --select would silently erase every
        other rule's grandfathered entries."""
        from znicz_tpu.analysis.__main__ import main

        with pytest.raises(SystemExit) as exc:
            main(
                [
                    "--write-baseline",
                    "--select",
                    "ZNC003",
                    "--baseline",
                    str(tmp_path / "b.json"),
                ]
            )
        assert exc.value.code == 2

    def test_write_baseline_refuses_path_subset(self, tmp_path):
        """A subset-path regen would erase other files' grandfathered
        entries."""
        from znicz_tpu.analysis.__main__ import main

        with pytest.raises(SystemExit) as exc:
            main(
                [
                    "--write-baseline",
                    "--baseline",
                    str(tmp_path / "b.json"),
                    "znicz_tpu/services",
                ]
            )
        assert exc.value.code == 2

    def test_syntax_error_reported_as_znc000(self, tmp_path):
        bad = tmp_path / "bad.py"
        bad.write_text("def f(:\n")
        fs = engine.analyze_paths([str(bad)], root=str(tmp_path))
        assert [f.rule for f in fs] == ["ZNC000"]

    def test_nonexistent_path_is_an_error_not_clean(self, tmp_path):
        with pytest.raises(FileNotFoundError):
            engine.analyze_paths(
                [str(tmp_path / "no_such_dir")], root=str(tmp_path)
            )

    def test_findings_sorted_and_pathed_relative(self, tmp_path):
        mod = tmp_path / "m.py"
        mod.write_text(
            "def f():\n"
            "    try:\n"
            "        return 1\n"
            "    except Exception:\n"
            "        pass\n"
        )
        fs = engine.analyze_paths([str(mod)], root=str(tmp_path))
        assert fs[0].path == "m.py"


# -- project rules: ZNC014/ZNC015/ZNC016 ---------------------------------


def run_project(sources, rule_id):
    """Run ONE project rule over an in-memory multi-file project
    (``{rel_path: source}``), suppression applied — the harness for
    the dataflow/lock-order/blocking rules, which reason over the
    whole index instead of one module."""
    from znicz_tpu.analysis.project import (
        ProjectIndex,
        project_rule_findings,
    )

    idx = ProjectIndex("/proj")
    for rel, src in sources.items():
        idx.add_module(textwrap.dedent(src), rel)
    idx.link()
    rule = RULES[rule_id]()
    assert rule.project, f"{rule_id} is not a project rule"
    return project_rule_findings(idx, [rule]), idx


class TestRecompileHazard:
    def test_len_into_cache_key_fires(self):
        fs, _ = run_project(
            {
                "services/mod.py": """
                programs = {}

                def admit(prompt):
                    key = ("admit", len(prompt))
                    programs[key] = 1
                """
            },
            "ZNC014",
        )
        assert ids(fs) == ["ZNC014"]
        assert "len(...)" in fs[0].message
        assert "programs" in fs[0].message

    def test_bucketed_key_stays_quiet(self):
        fs, _ = run_project(
            {
                "services/mod.py": """
                LADDER = (16, 32, 64)
                programs = {}

                def bucket_for(n, ladder):
                    for rung in ladder:
                        if n <= rung:
                            return rung
                    return ladder[-1]

                def admit(prompt):
                    key = ("admit", bucket_for(len(prompt), LADDER))
                    programs[key] = 1
                """
            },
            "ZNC014",
        )
        assert fs == []

    def test_rebinding_through_bucket_is_flow_sensitive(self):
        """``n = len(p); n = bucket_for(n, L)`` must be bounded at
        later uses — the last textual assignment before the use wins."""
        fs, _ = run_project(
            {
                "services/mod.py": """
                cache = {}

                def admit(p):
                    n = len(p)
                    n = bucket_for(n, (8, 16))
                    cache[n] = 1
                """
            },
            "ZNC014",
        )
        assert fs == []

    def test_ledger_call_key_fires(self):
        fs, _ = run_project(
            {
                "services/engine.py": """
                class Engine:
                    def admit(self, prompt):
                        self._timed_program(
                            ("admit", len(prompt)), run, prompt
                        )
                """
            },
            "ZNC014",
        )
        assert ids(fs) == ["ZNC014"]
        assert "_timed_program" in fs[0].message

    def test_wallclock_static_arg_fires(self):
        fs, _ = run_project(
            {
                "pkg/mod.py": """
                import jax
                import time

                def step(x, n):
                    return x * n

                fast = jax.jit(step, static_argnums=(1,))

                def run(x):
                    return fast(x, int(time.time()))
                """
            },
            "ZNC014",
        )
        assert ids(fs) == ["ZNC014"]
        assert "wall-clock" in fs[0].message
        assert "static argument 'n'" in fs[0].message

    def test_static_arg_resolved_cross_module(self):
        fs, _ = run_project(
            {
                "liba.py": """
                def step(x, width):
                    return x * width
                """,
                "libb.py": """
                import jax
                import liba

                fast = jax.jit(liba.step, static_argnames=("width",))

                def run(x, prompt):
                    return fast(x, width=len(prompt))
                """,
            },
            "ZNC014",
        )
        assert ids(fs) == ["ZNC014"]
        assert fs[0].path == "libb.py"

    def test_interprocedural_param_taint_fires(self):
        """A helper sized by its parameter fires when a call site
        passes ``len(...)`` — the origin names the call site."""
        fs, _ = run_project(
            {
                "services/mod.py": """
                import numpy as np

                def make_buffer(n):
                    return np.zeros((n, 4))

                def admit(prompt):
                    return make_buffer(len(prompt))
                """
            },
            "ZNC014",
        )
        assert ids(fs) == ["ZNC014"]
        assert "via call at services/mod.py" in fs[0].message

    def test_shape_ctor_outside_serving_tier_stays_quiet(self):
        """Loader-tier dataset-sized host buffers are one-time
        allocations, not per-request compile drivers."""
        fs, _ = run_project(
            {
                "loader/mod.py": """
                import numpy as np

                def materialize(items):
                    return np.zeros((len(items), 4))
                """
            },
            "ZNC014",
        )
        assert fs == []

    def test_traced_context_shapes_stay_quiet(self):
        """``jnp.zeros(...)`` INSIDE jitted code is trace
        polymorphism, not a host recompile driver."""
        fs, _ = run_project(
            {
                "services/mod.py": """
                import jax
                import jax.numpy as jnp

                @jax.jit
                def step(xs):
                    return jnp.zeros((len(xs), 4))
                """
            },
            "ZNC014",
        )
        assert fs == []

    def test_min_clamp_is_a_boundary(self):
        fs, _ = run_project(
            {
                "services/mod.py": """
                cache = {}

                def admit(prompt):
                    cache[min(len(prompt), 64)] = 1
                """
            },
            "ZNC014",
        )
        assert fs == []

    def test_loop_counter_key_fires(self):
        fs, _ = run_project(
            {
                "services/mod.py": """
                cache = {}

                def admit(prompts):
                    for i, p in enumerate(prompts):
                        cache[("row", i)] = p
                """
            },
            "ZNC014",
        )
        assert ids(fs) == ["ZNC014"]
        assert "enumerate" in fs[0].message

    def test_unknown_provenance_stays_quiet(self):
        """Config plumbing (constructor params, fields with no
        stores) is UNKNOWN — never fired on."""
        fs, _ = run_project(
            {
                "services/mod.py": """
                class Engine:
                    def __init__(self, batch_size):
                        self.batch_size = batch_size
                        self._programs = {}

                    def admit(self):
                        self._programs[("chunk", self.batch_size)] = 1
                """
            },
            "ZNC014",
        )
        assert fs == []

    def test_pragma_suppresses(self):
        fs, _ = run_project(
            {
                "services/mod.py": """
                cache = {}

                def admit(prompt):
                    cache[len(prompt)] = 1  # znicz-check: disable=ZNC014
                """
            },
            "ZNC014",
        )
        assert fs == []


class TestLockOrder:
    CYCLE = """
        import threading

        class Engine:
            def __init__(self):
                self._lock = threading.Lock()
                self._stats_lock = threading.Lock()

            def tick(self):
                with self._lock:
                    with self._stats_lock:
                        pass

            def stats(self):
                with self._stats_lock:
                    with self._lock:
                        pass
        """

    def test_opposite_nesting_fires(self):
        fs, _ = run_project({"services/mod.py": self.CYCLE}, "ZNC015")
        assert ids(fs) == ["ZNC015"]
        assert "lock-order cycle" in fs[0].message
        assert "_lock" in fs[0].message and "_stats_lock" in fs[0].message

    def test_consistent_order_stays_quiet(self):
        fs, _ = run_project(
            {
                "services/mod.py": """
                import threading

                class Engine:
                    def __init__(self):
                        self._lock = threading.Lock()
                        self._stats_lock = threading.Lock()

                    def tick(self):
                        with self._lock:
                            with self._stats_lock:
                                pass

                    def stats(self):
                        with self._lock:
                            with self._stats_lock:
                                pass
                """
            },
            "ZNC015",
        )
        assert fs == []

    def test_cycle_through_method_call(self):
        fs, _ = run_project(
            {
                "services/mod.py": """
                import threading

                class Engine:
                    def __init__(self):
                        self._a = threading.Lock()
                        self._b_lock = threading.Lock()

                    def _grab_b(self):
                        with self._b_lock:
                            pass

                    def tick(self):
                        with self._a:
                            self._grab_b()

                    def other(self):
                        with self._b_lock:
                            with self._a:
                                pass
                """
            },
            "ZNC015",
        )
        assert ids(fs) == ["ZNC015"]
        assert "self._grab_b()" in fs[0].message

    def test_cross_class_cycle_via_typed_attr(self):
        """Router holds its lock and calls into the registry (which
        locks); a registry sweep hook calls back into the router —
        the classic cross-object deadlock."""
        fs, _ = run_project(
            {
                "cluster/router.py": """
                import threading
                from cluster.registry import Registry

                class Router:
                    def __init__(self):
                        self._rr_lock = threading.Lock()
                        self.registry = Registry(self)

                    def route(self):
                        with self._rr_lock:
                            self.registry.note()

                    def on_sweep(self):
                        with self._rr_lock:
                            pass
                """,
                "cluster/registry.py": """
                import threading

                class Registry:
                    def __init__(self, router):
                        self.router: "Router" = router
                        self._lock = threading.Lock()

                    def note(self):
                        with self._lock:
                            pass

                    def sweep(self):
                        with self._lock:
                            self.router.on_sweep()
                """,
                "cluster/__init__.py": "",
            },
            "ZNC015",
        )
        assert ids(fs) == ["ZNC015"]
        assert "Router._rr_lock" in fs[0].message
        assert "Registry._lock" in fs[0].message

    def test_self_reacquisition_fires(self):
        fs, _ = run_project(
            {
                "services/mod.py": """
                import threading

                class Door:
                    def __init__(self):
                        self._lock = threading.Lock()

                    def _inner(self):
                        with self._lock:
                            pass

                    def close(self):
                        with self._lock:
                            self._inner()
                """
            },
            "ZNC015",
        )
        assert ids(fs) == ["ZNC015"]
        assert "self-deadlock" in fs[0].message

    def test_rlock_reacquisition_stays_quiet(self):
        fs, _ = run_project(
            {
                "services/mod.py": """
                import threading

                class Door:
                    def __init__(self):
                        self._lock = threading.RLock()

                    def _inner(self):
                        with self._lock:
                            pass

                    def close(self):
                        with self._lock:
                            self._inner()
                """
            },
            "ZNC015",
        )
        assert fs == []

    def test_out_of_scope_module_stays_quiet(self):
        fs, _ = run_project({"workflow/mod.py": self.CYCLE}, "ZNC015")
        assert fs == []

    def test_pragma_suppresses(self):
        # the finding anchors at the FIRST edge's acquisition site (in
        # sorted lock order) — a pragma on that line suppresses it
        fired, _ = run_project({"services/mod.py": self.CYCLE}, "ZNC015")
        anchor_line = fired[0].line
        lines = textwrap.dedent(self.CYCLE).splitlines()
        lines[anchor_line - 1] += "  # znicz-check: disable=ZNC015"
        fs, _ = run_project(
            {"services/mod.py": "\n".join(lines)}, "ZNC015"
        )
        assert fs == []


class TestBlockingUnderLock:
    def test_sleep_under_lock_fires(self):
        fs, _ = run_project(
            {
                "services/mod.py": """
                import threading
                import time

                class Door:
                    def __init__(self):
                        self._lock = threading.Lock()

                    def tick(self):
                        with self._lock:
                            time.sleep(0.05)
                """
            },
            "ZNC016",
        )
        assert ids(fs) == ["ZNC016"]
        assert "time.sleep()" in fs[0].message

    def test_sleep_outside_lock_stays_quiet(self):
        fs, _ = run_project(
            {
                "services/mod.py": """
                import threading
                import time

                class Door:
                    def __init__(self):
                        self._lock = threading.Lock()
                        self.n = 0

                    def tick(self):
                        time.sleep(0.05)
                        with self._lock:
                            self.n += 1
                """
            },
            "ZNC016",
        )
        assert fs == []

    def test_urlopen_through_helper_fires_with_chain(self):
        fs, _ = run_project(
            {
                "services/mod.py": """
                import threading
                import urllib.request

                def push(url):
                    return urllib.request.urlopen(url, timeout=5)

                class Pusher:
                    def __init__(self):
                        self._lock = threading.Lock()
                        self.url = "http://x/push"

                    def flush(self):
                        with self._lock:
                            push(self.url)
                """
            },
            "ZNC016",
        )
        assert ids(fs) == ["ZNC016"]
        assert "urlopen" in fs[0].message
        assert "push()" in fs[0].message

    def test_queue_get_with_timeout_under_lock_fires(self):
        """A BOUNDED wait under a lock still stalls every peer for
        the bound — timeout does not excuse ZNC016 (unlike ZNC010)."""
        fs, _ = run_project(
            {
                "services/mod.py": """
                import threading

                class Door:
                    def __init__(self):
                        self._lock = threading.Lock()
                        self.q = make_queue()

                    def tick(self):
                        with self._lock:
                            return self.q.get(timeout=1.0)
                """
            },
            "ZNC016",
        )
        assert ids(fs) == ["ZNC016"]

    def test_dict_get_homonym_stays_quiet(self):
        fs, _ = run_project(
            {
                "services/mod.py": """
                import threading

                class Door:
                    def __init__(self):
                        self._lock = threading.Lock()
                        self.d = {}

                    def lookup(self, k):
                        with self._lock:
                            return self.d.get(k)
                """
            },
            "ZNC016",
        )
        assert fs == []

    def test_out_of_scope_stays_quiet(self):
        fs, _ = run_project(
            {
                "workflow/mod.py": """
                import threading
                import time

                class Door:
                    def __init__(self):
                        self._lock = threading.Lock()

                    def tick(self):
                        with self._lock:
                            time.sleep(0.05)
                """
            },
            "ZNC016",
        )
        assert fs == []

    def test_pragma_suppresses(self):
        fs, _ = run_project(
            {
                "services/mod.py": """
                import threading
                import time

                class Door:
                    def __init__(self):
                        self._lock = threading.Lock()

                    def tick(self):
                        with self._lock:
                            time.sleep(0.01)  # znicz-check: disable=ZNC016
                """
            },
            "ZNC016",
        )
        assert fs == []


class TestExplainExamples:
    """The --explain registry metadata is EXECUTABLE documentation:
    every rule ships a firing example and a minimally-edited quiet
    twin, and this test runs both — the one source of truth cannot
    drift from the analyzer's behavior."""

    @pytest.mark.parametrize("rule_id", sorted(RULES))
    def test_example_fires_and_quiet_twin_is_quiet(self, rule_id):
        from znicz_tpu.analysis.project import (
            ProjectIndex,
            project_rule_findings,
        )

        cls = RULES[rule_id]
        assert cls.example_fire.strip(), f"{rule_id} has no example"
        assert cls.example_quiet.strip(), f"{rule_id} has no quiet twin"

        def run_example(src):
            idx = ProjectIndex("/example")
            for rel, s in cls.example_support_files.items():
                idx.add_module(textwrap.dedent(s), rel)
            idx.add_module(textwrap.dedent(src), cls.example_path)
            idx.link()
            rule = cls()
            if cls.project:
                out = project_rule_findings(idx, [rule])
            else:
                out = []
                for info in idx.modules.values():
                    for f in rule.check(info):
                        if not info.suppressed(f):
                            out.append(f)
                out = idx.relocate(out)
            return [f for f in out if f.rule == rule_id]

        assert run_example(cls.example_fire), (
            f"{rule_id}'s example_fire does not fire"
        )
        assert run_example(cls.example_quiet) == [], (
            f"{rule_id}'s example_quiet fires"
        )

    def test_explain_cli_prints_examples(self, capsys):
        from znicz_tpu.analysis.__main__ import main

        rc = main(["--explain", "ZNC014"])
        assert rc == 0
        out = capsys.readouterr().out
        assert "ZNC014" in out
        assert "FIRES" in out and "QUIET" in out
        assert "bucket_for" in out

    def test_explain_unknown_rule_is_usage_error(self):
        from znicz_tpu.analysis.__main__ import main

        with pytest.raises(SystemExit) as exc:
            main(["--explain", "ZNC999"])
        assert exc.value.code == 2


class TestLockModelExceptHandlers:
    """Review regression: ExceptHandler (and match_case) bodies are
    neither stmt nor expr — a naive child partition routed them around
    the held-lock walk, blinding ZNC015/016 to exactly the error-path
    retry/backoff code where sleep-under-lock lives."""

    def test_blocking_inside_except_handler_fires(self):
        fs, _ = run_project(
            {
                "services/mod.py": """
                import threading
                import time

                class Door:
                    def __init__(self):
                        self._lock = threading.Lock()

                    def tick(self):
                        try:
                            work()
                        except Exception:
                            with self._lock:
                                time.sleep(0.05)
                """
            },
            "ZNC016",
        )
        assert ids(fs) == ["ZNC016"]

    def test_lock_order_inside_except_handler_fires(self):
        fs, _ = run_project(
            {
                "services/mod.py": """
                import threading

                class Door:
                    def __init__(self):
                        self._a_lock = threading.Lock()
                        self._b_lock = threading.Lock()

                    def tick(self):
                        with self._a_lock:
                            with self._b_lock:
                                pass

                    def recover(self):
                        try:
                            work()
                        except Exception:
                            with self._b_lock:
                                with self._a_lock:
                                    pass
                """
            },
            "ZNC015",
        )
        assert ids(fs) == ["ZNC015"]

    def test_blocking_inside_match_case_fires(self):
        fs, _ = run_project(
            {
                "services/mod.py": """
                import threading
                import time

                class Door:
                    def __init__(self):
                        self._lock = threading.Lock()

                    def tick(self, kind):
                        with self._lock:
                            match kind:
                                case "slow":
                                    time.sleep(0.05)
                                case _:
                                    pass
                """
            },
            "ZNC016",
        )
        assert ids(fs) == ["ZNC016"]
