"""The reduction from a trace to numbers, on a trace small enough to work
out by hand and on a trace recorded on the chip."""

import json
import os

import pytest

import toy  # noqa: F401  (puts benchmarks/ on the path)
from harness.loading import BENCH_DIR, load_module

reduce = load_module("trace", "reduce")


def _trace(ops0, modules0=(), ops1=None, host=()):
    planes = [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [list(e) for e in modules0]},
        {"name": "XLA Ops", "events": [list(e) for e in ops0]},
    ]}]
    if ops1 is not None:
        planes.append({"name": "/device:TPU:1", "lines": [
            {"name": "XLA Ops", "events": [list(e) for e in ops1]}]})
    planes.append({"name": "/host:CPU", "lines": [
        {"name": "python", "events": [list(e) for e in host]}]})
    return {"planes": planes}


def test_busy_gaps_and_programs_by_hand():
    # device 0: busy 0-4 ms and 6-10 ms (two overlapping ops merge), idle 4-6
    ops0 = [("fusion.1", 0e6, 4e6), ("convolution.2", 6e6, 3e6),
            ("copy.3", 8e6, 2e6)]
    modules = [("jit_train_acc(123)", 0e6, 4e6), ("jit_train_acc(123)", 6e6, 4e6)]
    # device 1: busy 0-10 ms
    ops1 = [("fusion.1", 0e6, 10e6)]
    host = [("train/dispatch/train", 3.5e6, 1e6), ("loader/fetch", 4.2e6, 1.7e6),
            ("$python_frame", 0, 10e6)]
    r = reduce.reduce(_trace(ops0, modules, ops1, host))
    assert r["window_s"] == pytest.approx(10e-3)
    assert r["busy_s"] == pytest.approx((8e-3 + 10e-3) / 2)
    assert r["n_devices"] == 2
    program = r["programs"]["jit_train_acc"]
    # two executions on one of two devices: counted per device
    assert program["executions"] == pytest.approx(1.0)
    assert program["device_s"] == pytest.approx(4e-3)
    # the one gap of device 0 (4-6 ms) is covered most by loader/fetch
    assert r["idle_gaps"] == [["loader/fetch", pytest.approx(2e-3)]]
    assert r["device_ops"][0] == ["fusion.1", pytest.approx((4e-3 + 10e-3) / 2)]
    assert r["collective_s"] == 0.0


def test_collective_exposure_by_hand():
    # async all-reduce from 2 ms (start) to 9 ms (end of done); compute
    # covers 0-5 ms, so 5-9 ms of it is exposed
    ops = [("fusion.1", 0e6, 5e6), ("all-reduce-start.7", 2e6, 0.1e6),
           ("all-reduce-done.7", 8.5e6, 0.5e6),
           ("all-gather.3", 10e6, 1e6), ("fusion.2", 10.5e6, 1.5e6)]
    r = reduce.reduce(_trace(ops))
    assert r["collective_s"] == pytest.approx(7e-3 + 1e-3)
    assert r["collective_exposed_s"] == pytest.approx(4e-3 + 0.5e-3)


def test_no_device_operation_reads_as_nothing():
    assert reduce.reduce(_trace([])) is None


def test_recorded_chip_trace():
    """90 ms of a trace of lm-serve-steady recorded on a TPU v5e (PR 23,
    cut with tools/trace_outline.py --slice-ms 40 130): five prefill
    chunks back to back, then the host's hand-over before the decode
    chunk.  The expectations stored in the file were checked by hand: the
    prefill program's seconds are its five "XLA Modules" events (4.61 +
    24.12 + 24.13 + 24.13 + 7.77 ms, the first and last clipped), busy is
    a sweep over the operation intervals done apart from the reduction."""
    path = os.path.join(BENCH_DIR, "trace", "fixture_trace.json")
    with open(path) as f:
        fixture = json.load(f)
    expected = fixture.pop("expected")
    r = reduce.reduce(fixture)
    assert r["n_devices"] == expected["n_devices"]
    assert r["busy_s"] == pytest.approx(expected["busy_s"], rel=1e-6)
    assert r["window_s"] == pytest.approx(expected["window_s"], rel=1e-6)
    for name, want in expected["programs"].items():
        assert r["programs"][name]["executions"] == pytest.approx(want["executions"])
        assert r["programs"][name]["device_s"] == pytest.approx(want["device_s"], rel=1e-6)
    assert r["device_ops"][0][0] == expected["top_op"]
    assert r["idle_gaps"][0][0] == expected["top_gap"]
