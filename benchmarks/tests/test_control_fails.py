"""The controls come out as NOT correct.  Each is the plain reference put
in the program's place one step of precision below the configuration:
float8 inputs to every conv and fc for ``alexnet`` (it states bfloat16
activations), and float8 inputs to every matrix product for ``lm-gpt2s``
(float32 storage, but products of bfloat16 inputs at the TPU's default
matmul precision; an all-bfloat16 control read only 1.5 x the program's
own error on the chip and cannot be told from it: PERF.md section 2).

These are the toy-size copies a test run can hold; the readings the
cells' limits were set from were taken on the chip at the cells' own
sizes (PERF.md section 2, ``tools/calibrate_*.py``).  At toy size on the
CPU the program itself computes in float32, so sound runs read ~0 and
the limits are toy limits (``toy.py``) between that and the controls."""

import jax.numpy as jnp

import toy
from drivers_access import serve, train
from harness.checks import Checks, float8


def test_alexnet_control_float8_fails_and_the_program_passes(tmp_path):
    cfg = toy.cnn_config()
    toy.point_model_file_at(cfg)
    workload = toy.train_workload()
    run = toy.make_run("toy-train", workload, cfg, cache_dir=tmp_path)
    live = train.setup(run)
    live.pop("wf")
    params0 = [
        {k: jnp.asarray(v) for k, v in layer.items()} for layer in live["params0"]
    ]
    import jax

    key = jax.random.wrap_key_data(jnp.asarray(live["key_data"]))
    want = train.follow(cfg, run.traffic, params0, key, live["records"])
    limits = run.traffic["limits"]

    sound = Checks()
    train.compare(
        sound, train.program_readings(cfg, live["params0"], live["records"]),
        want, limits,
    )
    assert sound.correct, sound.rows

    low = train.follow(
        cfg, run.traffic, params0, key, live["records"], cast=float8
    )
    control = Checks()
    train.compare(control, low, want, limits)
    assert not control.correct, control.rows


def test_lm_control_float8_fails_and_the_program_passes():
    cfg = toy.lm_config()
    workload = toy.serve_workload()
    mix = workload["traffic"]
    server = serve.Server(cfg, 77, mix["deadline_s"])
    try:
        server.warm(__import__("numpy").random.default_rng(1), 8)
        measured = serve.measure(server, mix, 77, 2.0)
    finally:
        server.close()
    good = serve.summarise(measured, 2.0, mix["deadline_s"])["good"]
    assert len(good) >= 6
    sound = serve.decide_correct(cfg, server.weights, good, 77, mix)
    assert sound.correct, sound.rows
    control = serve.decide_correct(
        cfg, server.weights, good, 77, mix, control=float8
    )
    assert not control.correct, control.rows
