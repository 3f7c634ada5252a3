"""Toy-size stand-ins for the two configurations, for tests on the CPU:
the same kinds of layer at widths a test run can hold."""

from __future__ import annotations

import argparse
import copy
import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (BENCH_DIR, os.path.dirname(BENCH_DIR)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from harness import loading  # noqa: E402

_LRN = {"type": "lrn", "n": 5, "alpha": 0.0001, "beta": 0.75, "k": 2.0}


def cnn_config() -> dict:
    cfg = copy.deepcopy(loading.load_json("configs", "alexnet.json"))
    cfg.update(
        name="toy-cnn", input_shape=[35, 35, 3], pack_size=40, n_classes=10,
        layers=[
            {"type": "conv", "n": 8, "k": 5, "stride": 2, "pad": 0, "w_std": 0.05},
            dict(_LRN),
            {"type": "max_pool", "k": 3, "stride": 2},
            {"type": "conv", "n": 16, "k": 3, "stride": 1, "pad": 1, "w_std": 0.05},
            {"type": "max_pool", "k": 3, "stride": 2},
            {"type": "fc", "n": 32, "w_std": 0.05},
            {"type": "dropout", "ratio": 0.5},
            {"type": "fc", "n": 10, "w_std": 0.05, "activation": "linear"},
        ],
    )
    return cfg


def program_layers(cfg: dict) -> list:
    """The configuration's layer list in the model file's own notation."""
    gd = {
        "learning_rate": cfg["optimizer"]["learning_rate"],
        "gradient_moment": cfg["optimizer"]["gradient_moment"],
        "weights_decay": cfg["optimizer"]["weights_decay"],
        "learning_rate_bias": cfg["optimizer"]["learning_rate_bias"],
        "weights_decay_bias": cfg["optimizer"]["weights_decay_bias"],
    }
    out = []
    for spec in cfg["layers"]:
        kind = spec["type"]
        if kind == "conv":
            p = spec["pad"]
            out.append({
                "type": "conv_relu",
                "->": {"n_kernels": spec["n"], "kx": spec["k"], "ky": spec["k"],
                       "sliding": (spec["stride"],) * 2, "padding": (p, p, p, p),
                       "weights_filling": "gaussian",
                       "weights_stddev": spec["w_std"]},
                "<-": gd,
            })
        elif kind == "lrn":
            out.append({"type": "norm", "->": {k: spec[k] for k in ("n", "alpha", "beta", "k")}})
        elif kind == "max_pool":
            out.append({"type": "max_pooling", "->": {"kx": spec["k"], "ky": spec["k"], "sliding": (spec["stride"],) * 2}})
        elif kind == "fc":
            linear = spec.get("activation") == "linear"
            out.append({
                "type": "softmax" if linear else "all2all_relu",
                "->": {"output_sample_shape": spec["n"],
                       "weights_filling": "gaussian",
                       "weights_stddev": spec["w_std"]},
                "<-": gd,
            })
        elif kind == "dropout":
            out.append({"type": "dropout", "->": {"dropout_ratio": spec["ratio"]}})
    return out


def point_model_file_at(cfg: dict) -> None:
    """Make ``znicz_tpu/models/alexnet.py`` build the toy: its layers and
    sizes are configuration, which is how a user would shrink it too."""
    from znicz_tpu.core.config import root
    from znicz_tpu.models import alexnet  # noqa: F401  (registers defaults)

    root.alexnet.update({
        # float32 activations: at a batch of 8 bfloat16's rounding is as
        # large as the control's, so the toy program computes in float32,
        # reads ~1e-6 against the reference, and the toy limits below sit
        # between that and the float8 control's ~0.05
        "compute_dtype": None,
        "layers": program_layers(cfg),
        "loader": {"image_size": cfg["input_shape"][0],
                   "pack_size": cfg["pack_size"],
                   "n_classes": cfg["n_classes"]},
    })


def train_workload(**traffic) -> dict:
    wl = copy.deepcopy(loading.load_json("workloads", "alexnet-streamed.json"))
    wl["traffic"].update(
        per_chip_batch=8, n_train_images=64, reference_rows=8,
        limits={
            "loss_rel_gap": 2e-5,
            "first_grad_norm_worst_leaf_gap": 0.005,
            "first_grad_worst_leaf_rel_diff": 0.02,
            "param_change_norm_worst_leaf_gap": 0.005,
            "param_change_worst_leaf_rel_diff": 0.02,
        },
    )
    wl["traffic"].update(traffic)
    return wl


def lm_config() -> dict:
    cfg = copy.deepcopy(loading.load_json("configs", "lm-gpt2s.json"))
    cfg.update(name="toy-lm", n_embd=64, n_layer=2, n_head=4, n_positions=128,
               vocab_size=512, n_inner=256)
    cfg["serving"] = {"max_seq": 128, "block_size": 8, "admit_every": 4, "slots": 8}
    return cfg


def serve_workload(**traffic) -> dict:
    wl = copy.deepcopy(loading.load_json("workloads", "lm-serve-steady.json"))
    wl["traffic"].update(
        arrivals={"process": "poisson", "rate_per_s": 6.0},
        prompt_tokens={"dist": "lognormal", "median": 24, "sigma": 0.7, "min": 8, "max": 64},
        answer_tokens={"dist": "lognormal", "median": 16, "sigma": 0.5, "min": 4, "max": 32},
        ramp_s=0.5,
    )
    wl["traffic"].update(traffic)
    return wl


def make_run(cell_name, workload, config, *, seed=3000000123, seconds=2.0,
             trace=0, cache_dir=None, chips=1):
    """A ``Run`` as ``run.main`` builds it, on whatever devices jax has."""
    import jax

    import run as run_module

    args = argparse.Namespace(seed=seed, seconds=seconds, trace=trace)
    r = run_module.Run(
        {"name": cell_name, "chips": chips}, workload, config, args,
        jax.devices()[:chips],
    )
    if cache_dir is not None:
        r.cache_dir = str(cache_dir)
    return r
