"""The tail of a conv stage (bias, activation, LRN) as one op.

``ops/normalization.act_lrn`` against the three separate layers it stands
for (values and every gradient), its Pallas kernels (interpreted here)
against its jnp twin in both layouts the conv hands it, what
``workflow/model.py:build`` fuses and what it leaves alone, and the op's
partitioning rule under a data-parallel mesh of the 8 forced host devices.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from znicz_tpu import observability
from znicz_tpu.core import backend, prng
from znicz_tpu.ops import conv, normalization
from znicz_tpu.workflow import model as model_lib

ACTIVATIONS = normalization.FUSED_ACTIVATIONS


@pytest.fixture
def kernel_path(monkeypatch):
    """The op picks its kernels as it would on the chip; they run
    interpreted."""
    monkeypatch.setattr(backend, "on_tpu", lambda: True)
    monkeypatch.setattr(backend, "pallas_interpret", lambda: True)


def _normal(seed, shape, dtype=jnp.float32):
    return jax.random.normal(jax.random.key(seed), shape, jnp.float32).astype(
        dtype
    )


def _close(got, want, dtype):
    # bf16: both sides round a float32 result once, a last place apart at most
    tol = dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(
        rtol=2e-4, atol=2e-5
    )
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), **tol
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("n", [4, 5])
@pytest.mark.parametrize("c", [32, 96, 256])
@pytest.mark.parametrize("activation", ACTIVATIONS)
def test_fused_stage_matches_the_three_layers(activation, c, n, dtype):
    """conv.apply_lrn == lrn(conv.apply(...)): the output and the gradients
    of the conv's weights, its bias and its input (so also of ``y``).  7 x 7
    positions of 3 images: 147 rows, which no tile divides."""
    x = _normal(1, (3, 9, 9, 4), dtype)
    params = {
        "weights": _normal(2, (3, 3, 4, c), dtype) * 0.3,
        "bias": _normal(3, (c,), dtype),
    }
    weigh = _normal(4, (3, 7, 7, c))
    kw = dict(alpha=0.05, beta=0.75, k=2.0, n=n)

    def fused(p, x):
        return conv.apply_lrn(p, x, activation=activation, **kw)

    def separate(p, x):
        return normalization.lrn(
            conv.apply(p, x, activation=activation), **kw
        )

    def loss(f):
        return lambda p, x: jnp.sum(weigh * f(p, x).astype(jnp.float32))

    got, want = fused(params, x), separate(params, x)
    assert got.dtype == dtype and got.shape == (3, 7, 7, c)
    _close(got, want, dtype)
    g_got = jax.grad(loss(fused), (0, 1))(params, x)
    g_want = jax.grad(loss(separate), (0, 1))(params, x)
    if dtype == jnp.bfloat16:
        # the separate layers round y + b and the activation to bf16 on the
        # way, the fused op does not, so the two differ by bf16 roundings
        # (and a strict relu's kink falls either way for a z near 0): hold
        # both to the float32 answer, the fused one about as closely
        f32 = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
        exact = jax.grad(loss(separate), (0, 1))(f32, x.astype(jnp.float32))
        for got_leaf, want_leaf, exact_leaf in zip(
            *map(jax.tree_util.tree_leaves, (g_got, g_want, exact))
        ):
            scale = float(jnp.abs(exact_leaf).max())
            err = lambda a: float(
                jnp.abs(a.astype(jnp.float32) - exact_leaf).max()
            ) / scale
            assert err(got_leaf) < 2.5 * err(want_leaf) + 0.01
        return
    for got_leaf, want_leaf in zip(
        jax.tree_util.tree_leaves(g_got), jax.tree_util.tree_leaves(g_want)
    ):
        scale = float(jnp.abs(want_leaf).max())
        np.testing.assert_allclose(
            np.asarray(got_leaf) / scale, np.asarray(want_leaf) / scale,
            atol=2e-5,
        )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("activation", ACTIVATIONS)
@pytest.mark.parametrize(
    "shape,channel_axis,n",
    [
        ((5, 5, 3, 32), 3, 5),  # "HWNC": channels on the lanes, odd sizes
        ((3, 3, 32, 256), 3, 4),  # two lane tiles, an even window
        ((3, 5, 96, 256), 2, 5),  # "HWCN": the batch on the lanes (conv1)
        ((3, 3, 20, 128), 2, 4),  # a channel count off the sublane tile
    ],
)
def test_kernels_interpreted_match_the_twin(
    shape, channel_axis, n, activation, dtype, monkeypatch
):
    y, g = _normal(5, shape, dtype), _normal(6, shape, dtype)
    b = _normal(7, (shape[channel_axis],))
    kw = dict(
        activation=activation, alpha=0.05, beta=0.75, k=2.0, n=n,
        channel_axis=channel_axis,
    )

    def both():
        out, vjp = jax.vjp(lambda y, b: normalization.act_lrn(y, b, **kw), y, b)
        return (out,) + vjp(g)

    assert normalization.act_lrn_path(shape, dtype, channel_axis) == "twin"
    twin = both()
    monkeypatch.setattr(backend, "on_tpu", lambda: True)
    monkeypatch.setattr(backend, "pallas_interpret", lambda: True)
    assert normalization.act_lrn_path(shape, dtype, channel_axis) == "pallas"
    kernel = both()
    for got, want in zip(kernel, twin):
        assert got.dtype == want.dtype and got.shape == want.shape
        scale = float(jnp.abs(want.astype(jnp.float32)).max())
        np.testing.assert_allclose(
            np.asarray(got, np.float32) / scale,
            np.asarray(want, np.float32) / scale,
            atol=1e-2 if dtype == jnp.bfloat16 else 1e-5,
        )


def test_an_activation_without_a_tail_is_refused():
    with pytest.raises(ValueError, match="no fused tail"):
        normalization.act_lrn(
            jnp.zeros((2, 2, 2, 8)), jnp.zeros((8,)), activation="sigmoid"
        )


# -- what build() fuses ------------------------------------------------------

def _conv(kind, n_kernels=8):
    return {"type": kind, "->": {"n_kernels": n_kernels, "kx": 3, "ky": 3}}


_NORM = {"type": "norm", "->": {"n": 5, "alpha": 1e-2, "beta": 0.75, "k": 2.0}}
_POOL = {"type": "max_pooling", "->": {"kx": 2, "ky": 2}}
_HEAD = {"type": "softmax", "->": {"output_sample_shape": 10}}


def _explicit(impl):
    return {"type": "norm", "->": dict(_NORM["->"], impl=impl)}


def _fused_count():
    metric = observability.get_registry().metrics().get(
        "znicz_model_fused_conv_tails_total"
    )
    if metric is None:
        return {}
    return {k: c.value for k, c in metric.children().items() if c.value}


def _cifar_layers():
    from znicz_tpu.models import cifar

    return cifar.DEFAULTS["layers"], (32, 32, 3)


def _alexnet_layers():
    from znicz_tpu.models import alexnet

    return alexnet.DEFAULTS["layers"], (227, 227, 3)


@pytest.mark.parametrize(
    "name,layers,fused",
    [
        ("conv_relu->norm", [_conv("conv_relu"), _NORM, _POOL, _HEAD], 1),
        ("two stages", [_conv("conv_relu"), _NORM, _POOL,
                        _conv("conv_tanh", 16), _NORM, _HEAD], 2),
        ("conv_str->norm", [_conv("conv_str"), _NORM, _HEAD], 1),
        ("conv->norm", [_conv("conv"), _NORM, _HEAD], 1),
        ("conv->pool->norm", [_conv("conv_relu"), _POOL, _NORM, _HEAD], 0),
        ("explicit xla", [_conv("conv_relu"), _explicit("xla"), _HEAD], 0),
        ("explicit pallas", [_conv("conv_relu"), _explicit("pallas"), _HEAD], 0),
        ("conv_sigmoid->norm", [_conv("conv_sigmoid"), _NORM, _HEAD], 0),
        ("models/cifar.py", _cifar_layers, 0),
        ("models/alexnet.py", _alexnet_layers, 2),
    ],
)
def test_build_fuses_a_conv_directly_followed_by_norm(name, layers, fused):
    """The counter says how many stages were fused; the model computes what
    the unfused list computes (every ``norm`` given an explicit ``impl``
    keeps the separate layers) from the same parameter tree."""
    layers, input_shape = layers() if callable(layers) else (
        layers, (10, 10, 3)
    )
    observability.get_registry().reset()
    prng.seed_all(11)
    model = model_lib.build(layers, input_shape)
    assert sum(_fused_count().values()) == fused
    if fused:
        assert {k[1] for k in _fused_count()} == {"twin"}

    unfused = [
        {**spec, "->": dict(spec["->"], impl="xla")}
        if spec["type"] == "norm" and "impl" not in spec["->"] else spec
        for spec in layers
    ]
    prng.seed_all(11)
    plain = model_lib.build(unfused, input_shape)
    assert model.layer_types == plain.layer_types
    assert model.hyper == plain.hyper
    jax.tree_util.tree_map(np.testing.assert_array_equal, model.params, plain.params)
    assert [s["type"] for s in model.layer_specs] == [s["type"] for s in layers]
    if input_shape[0] > 32:
        return  # AlexNet at 227 x 227: the structure is the test
    x = _normal(12, (4,) + tuple(input_shape))
    np.testing.assert_allclose(
        model.apply(model.params, x), plain.apply(plain.params, x),
        rtol=1e-4, atol=1e-5,
    )
    loss = lambda m: lambda p: jnp.sum(jnp.square(m.apply(p, x)))
    for got, want in zip(
        jax.tree_util.tree_leaves(jax.grad(loss(model))(model.params)),
        jax.tree_util.tree_leaves(jax.grad(loss(plain))(plain.params)),
    ):
        np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-5)


def test_a_snapshot_of_a_fused_model_round_trips(tmp_path):
    """One entry a layer in params, hyper and types: a snapshot written by a
    model whose stages are fused restores into one, and into the unfused
    list, bit for bit."""
    from znicz_tpu.loader import datasets
    from znicz_tpu.workflow import StandardWorkflow
    from znicz_tpu.workflow.snapshotter import (
        Snapshotter, find_latest_valid, load_snapshot,
    )

    layers = [_conv("conv_relu"), _NORM, _POOL, _HEAD]

    def workflow(layer_list, **init):
        prng.seed_all(21)
        wf = StandardWorkflow(
            datasets.mnist(n_train=64, n_test=0, minibatch_size=32, flat=False),
            layer_list,
            decision_config={"max_epochs": 1},
        )
        if not init:
            wf.snapshotter = Snapshotter(str(tmp_path), "tail", interval=1)
        wf.initialize(seed=21, **init)
        return wf

    workflow(layers).run()
    path = find_latest_valid(str(tmp_path), prefix="tail")
    assert path is not None
    want = jax.device_get(load_snapshot(path)[0].params)
    assert [sorted(layer) for layer in want] == [
        ["bias", "weights"], [], [], ["bias", "weights"],
    ]
    for layer_list in (layers, [layers[0], _explicit("xla")] + layers[2:]):
        again = workflow(layer_list, snapshot=path)
        jax.tree_util.tree_map(
            np.testing.assert_array_equal,
            jax.device_get(again.state.params), want,
        )


# -- the op under a data-parallel mesh ---------------------------------------

def _toy_alexnet(c1):
    return [
        {"type": "conv_relu", "->": {"n_kernels": c1, "kx": 5, "ky": 5,
                                     "sliding": (2, 2)}},
        _NORM, _POOL,
        {"type": "conv_relu", "->": {"n_kernels": 128, "kx": 3, "ky": 3,
                                     "padding": (1, 1, 1, 1)}},
        _NORM, _POOL,
        {"type": "all2all_relu", "->": {"output_sample_shape": 32}},
        _HEAD,
    ]


class _Lowered:
    """Stands in for the workflow's jitted step: compiles the first call's
    program to text before passing every call through."""

    def __init__(self, step):
        self.step, self.text, self.watch = step, None, None

    def __call__(self, *args):
        if self.text is None:
            lowered = self.step.lower(*args)
            self.manual = lowered.as_text().count("sdy.manual_computation")
            self.text = lowered.compile().as_text()
        out = self.step(*args)
        if self.watch is None:
            self.watch = np.asarray(out[2])
        return out


@pytest.mark.parametrize(
    "batch,c1,order",
    [(16, 8, "HWNC"), (1024, 8, "HWCN")],
    ids=["channels-on-lanes", "batch-on-lanes"],
)
def test_data_parallel_step_keeps_the_batch_sharded(
    batch, c1, order, kernel_path
):
    """An AlexNet-shaped step (toy widths) under DataParallel over 8 devices,
    with the op's kernels in the program as on the chip: no activation is
    gathered (each kernel call is a per-shard region under the mesh
    ``DataParallel.scope()`` hands the trace), and the step's loss, gradient
    norm and updated parameters are the single-device step's."""
    from znicz_tpu.loader import datasets
    from znicz_tpu.parallel import DataParallel, make_mesh
    from znicz_tpu.workflow import StandardWorkflow

    def run(parallel):
        prng.seed_all(31)
        wf = StandardWorkflow(
            datasets.mnist(
                n_train=batch, n_test=0, minibatch_size=batch, flat=False
            ),
            _toy_alexnet(c1),
            decision_config={"max_epochs": 1},
            default_hyper={"learning_rate": 0.05, "gradient_moment": 0.9},
        )
        wf.parallel = parallel
        wf.initialize(seed=31)
        wf._train_step = _Lowered(wf._train_step)
        wf.run_epoch()
        wf.sync_epoch()
        return wf._train_step, jax.device_get(wf.state.params)

    seen = []
    real = normalization.act_lrn

    def spy(y, b, **kw):
        seen.append("".join(
            "C" if a == kw["channel_axis"] % y.ndim
            else "N" if d == batch else "."
            for a, d in enumerate(y.shape)
        ))
        return real(y, b, **kw)

    normalization.act_lrn = spy
    try:
        single, want = run(None)
        sharded, got = run(DataParallel(make_mesh(8, 1)))
    finally:
        normalization.act_lrn = real
    # conv1's stage in the order under test; conv2's 128 channels fill a
    # lane tile, so they go last whatever the batch
    assert seen[0] == {"HWNC": "..NC", "HWCN": "..CN"}[order], seen
    assert seen[1] == "..NC", seen

    # two stages, a forward and a backward kernel each
    assert (single.manual, sharded.manual) == (0, 4)
    for line in sharded.text.splitlines():
        if "all-gather" in line and "= " in line:
            shape = line.split("= ", 1)[1].split("{", 1)[0]
            dims = [int(d) for d in shape.split("[", 1)[1].rstrip("]").split(",") if d]
            assert len(dims) < 3, f"an activation is gathered: {line.strip()[:200]}"
    np.testing.assert_allclose(sharded.watch, single.watch, rtol=1e-4)
    for got_leaf, want_leaf in zip(
        jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)
    ):
        np.testing.assert_allclose(got_leaf, want_leaf, rtol=1e-3, atol=1e-6)
