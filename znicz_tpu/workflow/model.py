"""Declarative layer-list -> pure model compiler.

Capability parity with ``znicz/standard_workflow.py``'s declarative
``layers=[{"type": "conv", ...}, ...]`` config [SURVEY.md 2.3 "Standard
workflow builder"], including the reference's layer-spec shape: ``"type"``,
``"->"`` (forward knobs) and ``"<-"`` (gradient-descent knobs — here they
become the per-layer :class:`~znicz_tpu.nn.optimizer.HyperParams`).

A model is ``params`` (list of per-layer dicts, a pytree) plus a pure
``apply(params, x, train, rng)`` closure; shape inference runs at build time
so every parameter is initialized eagerly from the named PRNG, exactly one
draw sequence per config (reference reproducibility contract).
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from znicz_tpu import observability
from znicz_tpu.core import backend
from znicz_tpu.nn import optimizer
from znicz_tpu.ops import (
    activation as act_op,
    all2all,
    attention as attention_op,
    conv,
    cutter,
    deconv,
    dropout as dropout_op,
    moe as moe_op,
    normalization,
    pooling,
)


class Model(NamedTuple):
    params: List[Dict[str, jnp.ndarray]]
    apply: Callable  # (params, x, *, train=False, rng=None) -> output
    hyper: List[optimizer.HyperParams]
    layer_types: Tuple[str, ...]
    input_shape: Tuple[int, ...]  # per-sample shape (no batch dim)
    output_shape: Tuple[int, ...]
    returns_logits: bool  # final "softmax" layer emits logits (CE wants them)
    compute_dtype: Optional[Any] = None  # bf16 mixed precision when set
    layer_specs: Tuple[dict, ...] = ()  # original declarative specs (export)

    def predict(self, params, x):
        """Inference output: probabilities for softmax-headed models."""
        y = self.apply(params, x, train=False)
        return jax.nn.softmax(y, axis=-1) if self.returns_logits else y


def _split_spec(spec: Dict[str, Any]) -> Tuple[str, dict, dict]:
    spec = dict(spec)
    kind = spec.pop("type")
    fwd = dict(spec.pop("->", {}))
    bwd = dict(spec.pop("<-", {}))
    spec.pop("name", None)
    fwd.update(spec)  # flat kwargs are forward knobs
    return kind, fwd, bwd


def _n_output(fwd: dict) -> int:
    # reference name: output_sample_shape (int or shape tuple)
    n = fwd.get("output_sample_shape", fwd.get("n_output"))
    if n is None:
        raise ValueError(
            "all2all layer needs output_sample_shape (or n_output)"
        )
    return int(np.prod(n))


_A2A_ACT = {
    "all2all": "linear",
    "all2all_tanh": "tanh",
    "all2all_relu": "relu",
    "all2all_str": "strict_relu",
    "all2all_sigmoid": "sigmoid",
}
_CONV_ACT = {
    "conv": "linear",
    "conv_tanh": "tanh",
    "conv_relu": "relu",
    "conv_str": "strict_relu",
    "conv_sigmoid": "sigmoid",
}
_POOL = {
    "max_pooling": pooling.max_pool,
    "avg_pooling": pooling.avg_pool,
    "maxabs_pooling": pooling.max_abs_pool,
}
_INIT_KEYS = (
    "weights_stddev",
    "bias_stddev",
    "weights_filling",
    "bias_filling",
)


_LRN_KEYS = ("alpha", "beta", "k", "n")


def _fused_tail(kind: str, following) -> Optional[dict]:
    """The LRN keywords of ``following`` if it is a ``norm`` layer whose tail
    a ``kind`` conv layer can take over (ops/conv.py:apply_lrn), else None.
    Decided from the layer list alone: a ``norm`` with an explicit ``impl``
    keeps the implementation it names, and an activation the fused op does
    not carry keeps the separate layers."""
    if following is None or kind not in _CONV_ACT:
        return None
    next_kind, fwd, _ = _split_spec(following)
    if next_kind != "norm" or "impl" in fwd:
        return None
    if _CONV_ACT[kind] not in normalization.FUSED_ACTIVATIONS:
        return None
    return {k: fwd[k] for k in _LRN_KEYS if k in fwd}


def _init_kwargs(fwd: dict) -> dict:
    return {k: fwd[k] for k in _INIT_KEYS if k in fwd}


def _init_kwargs_moe(fwd: dict) -> dict:
    return {
        k: fwd[k]
        for k in ("weights_stddev", "weights_filling")
        if k in fwd
    }


def build(
    layers: Sequence[Dict[str, Any]],
    input_shape: Sequence[int],
    *,
    rand_name: str = "default",
    default_hyper: Optional[optimizer.HyperParams] = None,
    compute_dtype: Optional[Any] = None,
) -> Model:
    """Compile a layer list into a Model.

    ``input_shape`` is the per-sample shape: ``(features,)`` for MLPs,
    ``(H, W, C)`` for conv stacks (NHWC).

    ``compute_dtype`` (e.g. ``jnp.bfloat16``): mixed precision — params stay
    float32 (master weights for the update rule) but are cast per layer, and
    activations flow in the compute dtype; matmul/conv accumulation remains
    f32 via ``preferred_element_type``.  Halves HBM traffic for activations,
    which is the TPU bottleneck for conv nets (MXU already multiplies in
    bf16 either way).  The output is cast back to f32 for the loss.
    """
    default_hyper = default_hyper or optimizer.HyperParams()
    params: List[Dict[str, jnp.ndarray]] = []
    hyper: List[optimizer.HyperParams] = []
    fns: List[Callable] = []  # (params, x, train, rng) -> x
    types: List[str] = []
    shape = (1,) + tuple(int(s) for s in input_shape)  # batch placeholder
    returns_logits = False
    tail = None  # LRN keywords the previous (conv) layer already applied

    for i, spec in enumerate(layers):
        kind, fwd, bwd = _split_spec(spec)
        taken, tail = tail, _fused_tail(
            kind, layers[i + 1] if i + 1 < len(layers) else None
        )
        h = default_hyper._replace(**bwd) if bwd else default_hyper
        returns_logits = False

        if kind in _A2A_ACT or kind == "softmax":
            n_in = int(np.prod(shape[1:]))
            n_out = _n_output(fwd)
            p = all2all.init_params(
                n_in, n_out, rand_name=rand_name, **_init_kwargs(fwd)
            )
            activation = _A2A_ACT.get(kind, "linear")
            include_bias = fwd.get("include_bias", True)

            def fn(p, x, train, rng, activation=activation, ib=include_bias):
                return all2all.apply(
                    p, x, activation=activation, include_bias=ib
                )

            shape = (shape[0], n_out)
            returns_logits = kind == "softmax"

        elif kind in _CONV_ACT:
            if len(shape) != 4:
                raise ValueError(
                    f"layer {i} ({kind}) needs NHWC input, got shape {shape}"
                )
            n_kernels = int(fwd["n_kernels"])
            kx, ky = int(fwd["kx"]), int(fwd["ky"])
            sliding = tuple(fwd.get("sliding", (1, 1)))
            padding = fwd.get("padding", (0, 0, 0, 0))
            p = conv.init_params(
                shape[3], n_kernels, kx, ky,
                rand_name=rand_name, **_init_kwargs(fwd),
            )
            activation = _CONV_ACT[kind]
            if tail is None:

                def fn(p, x, train, rng, s=sliding, pad=padding, a=activation):
                    return conv.apply(
                        p, x, sliding=s, padding=pad, activation=a
                    )

            else:
                # the stage's tail (bias, activation, the next layer's LRN)
                # is one op here; the norm slot below passes through
                observability.counter(
                    "znicz_model_fused_conv_tails_total",
                    "conv stages whose bias, activation and LRN build() "
                    "compiled into the one op ops/normalization.act_lrn",
                    ("activation", "path"),
                ).labels(
                    activation=activation,
                    path="pallas" if backend.on_tpu() else "twin",
                ).inc()

                def fn(p, x, train, rng, s=sliding, pad=padding,
                       a=activation, kw=tail):
                    return conv.apply_lrn(
                        p, x, sliding=s, padding=pad, activation=a, **kw
                    )

            shape = conv.output_shape(
                shape, n_kernels, kx, ky, sliding, padding
            )

        elif kind in _POOL or kind == "stochastic_pooling":
            kx, ky = int(fwd["kx"]), int(fwd["ky"])
            sliding = fwd.get("sliding")
            if sliding is not None:
                sliding = tuple(sliding)
            p = {}
            if kind == "stochastic_pooling":

                def fn(p, x, train, rng, kx=kx, ky=ky, s=sliding):
                    return pooling.stochastic_pool(
                        x, kx, ky, s, rng=rng, train=train
                    )

            else:
                pool_fn = _POOL[kind]

                def fn(p, x, train, rng, f=pool_fn, kx=kx, ky=ky, s=sliding):
                    return f(x, kx, ky, s)

            shape = pooling.output_shape(shape, kx, ky, sliding)

        elif kind == "deconv":
            n_channels = int(fwd["n_channels"])
            kx, ky = int(fwd["kx"]), int(fwd["ky"])
            sliding = tuple(fwd.get("sliding", (1, 1)))
            padding = fwd.get("padding", (0, 0, 0, 0))
            p = deconv.init_params(
                n_channels, shape[3], kx, ky,
                rand_name=rand_name, **_init_kwargs(fwd),
            )

            def fn(p, x, train, rng, s=sliding, pad=padding):
                return deconv.apply(p, x, sliding=s, padding=pad)

            out = deconv.apply(
                p, jnp.zeros(shape, jnp.float32), sliding=sliding, padding=padding
            )
            shape = tuple(out.shape)

        elif kind == "norm":
            p = {}
            kwargs = {k: fwd[k] for k in _LRN_KEYS + ("impl",) if k in fwd}

            if taken is not None:

                def fn(p, x, train, rng):
                    return x

            else:

                def fn(p, x, train, rng, kw=kwargs):
                    return normalization.lrn(x, **kw)

        elif kind == "dropout":
            p = {}
            ratio = float(fwd.get("dropout_ratio", 0.5))

            def fn(p, x, train, rng, r=ratio):
                return dropout_op.dropout(
                    x, dropout_ratio=r, rng=rng, train=train
                )

        elif kind == "cutter":
            p = {}
            padding = fwd["padding"]

            def fn(p, x, train, rng, pad=padding):
                return cutter.cut(x, pad)

            shape = cutter.output_shape(shape, padding)

        elif kind.startswith("activation_"):
            p = {}
            a = act_op.get(kind[len("activation_"):])

            def fn(p, x, train, rng, a=a):
                return a(x)

        elif kind == "moe":
            # residual mixture-of-experts FFN (ops/moe.py): works on [B, F]
            # activations or per-token on [B, T, D] sequences.  Output dim ==
            # input dim, combined residually, so it drops into any stack.
            d = shape[-1] if len(shape) == 3 else int(np.prod(shape[1:]))
            n_experts = int(fwd["n_experts"])
            n_hidden = int(fwd.get("n_hidden", 4 * d))
            top_k = int(fwd.get("top_k", 1))
            residual = bool(fwd.get("residual", True))
            # dense dispatch through E=16 (exact math, MXU-friendly — see
            # ops/moe.py), capacity-bounded token-drop dispatch above;
            # "dispatch" overrides either way
            dispatch = fwd.get(
                "dispatch", "dense" if n_experts <= 16 else "capacity"
            )
            cap_factor = float(fwd.get("capacity_factor", 1.25))
            p = moe_op.init_params(
                d, n_hidden, n_experts,
                rand_name=rand_name, **_init_kwargs_moe(fwd),
            )

            def fn(p, x, train, rng, k=top_k, res=residual,
                   disp=dispatch, cf=cap_factor):
                if x.ndim == 3:  # per-token on sequences
                    b, t, dd = x.shape
                    y = moe_op.apply(
                        p, x.reshape(b * t, dd), top_k=k,
                        dispatch=disp, capacity_factor=cf,
                    ).reshape(b, t, dd)
                    return x + y if res else y
                flat = x.reshape(x.shape[0], -1)
                y = moe_op.apply(
                    p, flat, top_k=k, dispatch=disp, capacity_factor=cf
                )
                return flat + y if res else y

            if len(shape) != 3:  # flattened-token path emits [B, d]
                shape = (shape[0], d)

        elif kind == "attention":
            # pre-LN residual multi-head self-attention block
            # (ops/attention.py): per-sample input must be [T, D]
            if len(shape) != 3:
                raise ValueError(
                    f"layer {i} (attention) needs [T, D] per-sample input, "
                    f"got shape {shape}"
                )
            d = shape[2]
            n_heads = int(fwd.get("n_heads", 4))
            causal = bool(fwd.get("causal", True))
            p = attention_op.init_mha_params(
                d, n_heads, rand_name=rand_name, **_init_kwargs(fwd)
            )
            p["ln_scale"] = jnp.ones((d,))
            p["ln_bias"] = jnp.zeros((d,))

            def fn(p, x, train, rng, nh=n_heads, c=causal):
                h = normalization.layer_norm(x, p["ln_scale"], p["ln_bias"])
                return x + attention_op.mha(p, h, n_heads=nh, causal=c)

        else:
            raise ValueError(
                f"unknown layer type {kind!r} at index {i}; known: "
                f"{sorted(_A2A_ACT) + sorted(_CONV_ACT) + sorted(_POOL) + ['softmax', 'stochastic_pooling', 'deconv', 'norm', 'dropout', 'cutter', 'moe', 'attention', 'activation_*']}"
            )

        params.append(p)
        hyper.append(h)
        fns.append(fn)
        types.append(kind)

    needs_rng = tuple(
        t in ("dropout", "stochastic_pooling") for t in types
    )

    def apply(params, x, *, train: bool = False, rng: Optional[jax.Array] = None):
        keys = [None] * len(fns)
        if train and any(needs_rng):
            if rng is None:
                raise ValueError(
                    "model has dropout/stochastic layers: apply(train=True) "
                    "needs an rng key"
                )
            split = jax.random.split(rng, len(fns))
            keys = [split[i] if needs_rng[i] else None for i in range(len(fns))]
        if compute_dtype is not None:
            x = x.astype(compute_dtype)
            params = jax.tree_util.tree_map(
                lambda w: w.astype(compute_dtype), params
            )
        for fn, p, k in zip(fns, params, keys):
            x = fn(p, x, train, k)
        if compute_dtype is not None:
            x = x.astype(jnp.float32)
        return x

    return Model(
        params=params,
        apply=apply,
        hyper=hyper,
        layer_types=tuple(types),
        input_shape=tuple(int(s) for s in input_shape),
        output_shape=tuple(shape[1:]),
        returns_logits=returns_logits,
        compute_dtype=compute_dtype,
        layer_specs=tuple(
            {"type": t, **_split_spec(s)[1]}
            for t, s in zip(types, layers)
        ),
    )
