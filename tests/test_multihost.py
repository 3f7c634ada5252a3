"""Multi-host bring-up proof: 2 real processes, localhost coordinator, CPU.

Exercises the reference's master/slave replacement end to end [SURVEY.md 3.4
``--listen``/``--master-address`` -> ``--coordinator``/``--num-processes``/
``--process-id``]: both processes rendezvous via ``jax.distributed``, build
ONE global mesh spanning both, and run a jitted cross-process reduction.
"""

import os
import socket
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

WORKER = r"""
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.pop("XLA_FLAGS", None)  # 1 local device per process
sys.path.insert(0, sys.argv[3])

import jax

from znicz_tpu.parallel import multihost

info = multihost.initialize(
    coordinator_address=sys.argv[1],
    num_processes=2,
    process_id=int(sys.argv[2]),
)
assert info["process_count"] == 2, info
assert info["global_devices"] == 2, info

import numpy as np
import jax.numpy as jnp
from jax.experimental import multihost_utils
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# exactly-one-coordinator contract (reference: master does bookkeeping)
flags = multihost_utils.process_allgather(
    jnp.asarray([1.0 if multihost.is_coordinator() else 0.0])
)
assert float(np.sum(flags)) == 1.0, flags

# jitted cross-process reduction over the global mesh
mesh = Mesh(np.array(jax.devices()), ("data",))
local = jnp.ones((4,)) * (jax.process_index() + 1)
garr = multihost_utils.host_local_array_to_global_array(local, mesh, P("data"))
total = jax.jit(
    jnp.sum, out_shardings=NamedSharding(mesh, P())
)(garr)
assert float(total) == 12.0, float(total)  # 4*1 + 4*2
print(f"OK process={jax.process_index()}")
"""


TRAIN_WORKER = r"""
import json, os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.pop("XLA_FLAGS", None)  # 1 local device per process
sys.path.insert(0, sys.argv[4])

import jax

from znicz_tpu.parallel import multihost

info = multihost.initialize(
    coordinator_address=sys.argv[1],
    num_processes=2,
    process_id=int(sys.argv[2]),
)
assert info["global_devices"] == 2, info

import numpy as np
from znicz_tpu.core import prng
from znicz_tpu.loader import datasets
from znicz_tpu.parallel import DataParallel, make_mesh
from znicz_tpu.workflow import StandardWorkflow
from znicz_tpu.workflow.snapshotter import Snapshotter

snap_root = sys.argv[3]
prng.seed_all(99)
loader = datasets.mnist(n_train=256, n_test=64, minibatch_size=64)
wf = StandardWorkflow(
    loader,
    [
        {"type": "all2all_tanh", "->": {"output_sample_shape": 32}},
        {"type": "softmax", "->": {"output_sample_shape": 10}},
    ],
    decision_config={"max_epochs": 3},
    default_hyper={"learning_rate": 0.1, "gradient_moment": 0.9},
)
wf.parallel = DataParallel(make_mesh(2, 1))
# separate per-process dirs: proves only the coordinator ever writes
wf.snapshotter = Snapshotter(
    os.path.join(snap_root, f"proc{jax.process_index()}"), interval=1
)
wf.initialize(seed=99)
# the loader must be serving this process's half of each global minibatch
assert wf.loader.process_count == 2, wf.loader.process_count
dec = wf.run()
hist = [
    {
        "train_loss": e["train"]["loss"],
        "train_n_err": e["train"]["n_err"],
        "test_n_err": e["test"]["n_err"],
    }
    for e in dec.history
]
print("HIST" + str(jax.process_index()) + "=" + json.dumps(hist))
print(f"OK process={jax.process_index()}")
"""


SCAN_WORKER = r"""
import json, os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.pop("XLA_FLAGS", None)
sys.path.insert(0, sys.argv[3])

import jax

from znicz_tpu.parallel import multihost

multihost.initialize(
    coordinator_address=sys.argv[1], num_processes=2,
    process_id=int(sys.argv[2]),
)

import numpy as np
from znicz_tpu.core import prng
from znicz_tpu.loader import FullBatchLoader
from znicz_tpu.parallel import DataParallel, make_mesh
from znicz_tpu.workflow import StandardWorkflow

gen = np.random.default_rng(0)
imgs = gen.integers(0, 256, (256, 64), dtype=np.uint8)
labels = gen.integers(0, 10, 256).astype(np.int32)
prng.seed_all(77)
loader = FullBatchLoader(
    {"train": imgs}, {"train": labels}, minibatch_size=64,
    normalization="range", normalization_kwargs={"scale": 255.0,
                                                 "shift": -0.5},
    device_resident=True,
)
wf = StandardWorkflow(
    loader,
    [
        {"type": "all2all_tanh", "->": {"output_sample_shape": 32}},
        {"type": "softmax", "->": {"output_sample_shape": 10}},
    ],
    decision_config={"max_epochs": 3},
    default_hyper={"learning_rate": 0.1, "gradient_moment": 0.9},
)
wf.parallel = DataParallel(make_mesh(2, 1))
wf.initialize(seed=77)
assert wf._use_epoch_scan(), "device-resident loader must take the scan path"
dec = wf.run()
hist = [e["train"]["loss"] for e in dec.history]
print("HIST" + str(jax.process_index()) + "=" + json.dumps(hist))
print(f"OK process={jax.process_index()}")
"""


TP_CONV_WORKER = r"""
import json, os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
# 2 LOCAL devices per process -> 4 global: the mesh's model axis spans
# devices WITHIN a process, data axis spans processes
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
sys.path.insert(0, sys.argv[4])

import jax

from znicz_tpu.parallel import multihost

info = multihost.initialize(
    coordinator_address=sys.argv[1], num_processes=2,
    process_id=int(sys.argv[2]),
)
assert info["global_devices"] == 4, info

import numpy as np
from znicz_tpu.core import prng
from znicz_tpu.loader import datasets
from znicz_tpu.parallel import DataParallel, make_mesh
from znicz_tpu.workflow import StandardWorkflow
from znicz_tpu.workflow.snapshotter import Snapshotter

snap_dir = sys.argv[3]
prng.seed_all(55)
loader = datasets.mnist(n_train=128, n_test=0, minibatch_size=32, flat=False)
wf = StandardWorkflow(
    loader,
    [
        {"type": "conv_relu", "->": {"n_kernels": 8, "kx": 5, "ky": 5}},
        {"type": "max_pooling", "->": {"kx": 2, "ky": 2}},
        {"type": "conv_relu", "->": {"n_kernels": 16, "kx": 5, "ky": 5}},
        {"type": "max_pooling", "->": {"kx": 2, "ky": 2}},
        {"type": "softmax", "->": {"output_sample_shape": 10}},
    ],
    decision_config={"max_epochs": 3},
    default_hyper={"learning_rate": 0.05, "gradient_moment": 0.9},
)
wf.parallel = DataParallel(make_mesh(2, 2), tp=True)  # cnn_tp_rules auto
wf.snapshotter = Snapshotter(snap_dir, interval=1)
wf.initialize(seed=55)
# conv kernels really live sharded over model, ACROSS the two hosts
w0 = wf.state.params[0]["weights"]
assert not w0.is_fully_replicated, w0.sharding
assert not w0.is_fully_addressable  # spans both processes' devices
dec = wf.run()
hist = [e["train"]["loss"] for e in dec.history]
print("HIST" + str(jax.process_index()) + "=" + json.dumps(hist))
print(f"OK process={jax.process_index()}")
"""


POOL_SHARDED_WORKER = r"""
import json, os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
sys.path.insert(0, sys.argv[3])

import jax

from znicz_tpu.parallel import multihost

info = multihost.initialize(
    coordinator_address=sys.argv[1], num_processes=2,
    process_id=int(sys.argv[2]),
)
assert info["global_devices"] == 4, info

import numpy as np
from znicz_tpu.core import prng
from znicz_tpu.loader import FullBatchLoader
from znicz_tpu.parallel import DataParallel, make_mesh
from znicz_tpu.workflow import StandardWorkflow

gen = np.random.default_rng(3)
imgs = gen.integers(0, 256, (128, 8, 8, 1), dtype=np.uint8)
labels = (imgs.mean(axis=(1, 2, 3)) > 127).astype(np.int32)
prng.seed_all(67)
loader = FullBatchLoader(
    {"train": imgs}, {"train": labels}, minibatch_size=32,
    normalization="range",
    normalization_kwargs={"scale": 255.0, "shift": -0.5},
    device_resident=True, pool_sharded=True,
)
wf = StandardWorkflow(
    loader,
    [{"type": "all2all_tanh", "->": {"output_sample_shape": 8}},
     {"type": "softmax", "->": {"output_sample_shape": 2}}],
    decision_config={"max_epochs": 3},
    default_hyper={"learning_rate": 0.1, "gradient_moment": 0.9},
)
wf.parallel = DataParallel(make_mesh(4, 1))
wf.initialize(seed=67)
# each PROCESS shipped only its 2 shards' rows; the global pool spans all 4
pool = wf._ctx["pool"]
assert pool.shape[0] == 128
assert not pool.is_fully_addressable
assert pool.addressable_shards[0].data.shape[0] == 32
dec = wf.run()
hist = [e["train"]["loss"] for e in dec.history]
print("HIST" + str(jax.process_index()) + "=" + json.dumps(hist))
print(f"OK process={jax.process_index()}")
"""


KILL_WORKER = r"""
import json, os, signal, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.pop("XLA_FLAGS", None)
sys.path.insert(0, sys.argv[4])

import jax

from znicz_tpu.parallel import multihost

multihost.initialize(
    coordinator_address=sys.argv[1], num_processes=2,
    process_id=int(sys.argv[2]),
)

import numpy as np
from znicz_tpu.core import prng
from znicz_tpu.loader import datasets
from znicz_tpu.parallel import DataParallel, make_mesh
from znicz_tpu.workflow import StandardWorkflow
from znicz_tpu.workflow.snapshotter import Snapshotter

phase = sys.argv[5]  # "kill" or "resume"
snap_dir = sys.argv[3]
prng.seed_all(99)
loader = datasets.mnist(n_train=256, n_test=64, minibatch_size=64)
wf = StandardWorkflow(
    loader,
    [
        {"type": "all2all_tanh", "->": {"output_sample_shape": 32}},
        {"type": "softmax", "->": {"output_sample_shape": 10}},
    ],
    decision_config={"max_epochs": 5},
    default_hyper={"learning_rate": 0.1, "gradient_moment": 0.9},
)
wf.parallel = DataParallel(make_mesh(2, 1))
# ONE shared snapshot dir: only the coordinator's writer flag is set
wf.snapshotter = Snapshotter(snap_dir, interval=1)
if phase == "kill":
    wf.initialize(seed=99)
    for done in range(1, 6):
        v = wf.run_epoch()
        if jax.process_index() == 1 and done == 3:
            # hard failure mid-job: epoch 2's snapshot is durable, epoch 3
            # is in flight on the peer — the reference's dying-slave case
            os.kill(os.getpid(), signal.SIGKILL)
        if v["stop"]:
            break
else:
    wf.initialize(
        snapshot=os.path.join(snap_dir, "workflow_epoch2.pickle.gz")
    )
    assert wf.decision.epoch == 3, wf.decision.epoch
    dec = wf.run()
    hist = [
        {"train_loss": e["train"]["loss"], "train_n_err": e["train"]["n_err"]}
        for e in dec.history
    ]
    print("HIST" + str(jax.process_index()) + "=" + json.dumps(hist))
print(f"OK process={jax.process_index()}")
"""


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_two_process_localhost_rendezvous(tmp_path):
    addr = f"127.0.0.1:{_free_port()}"
    env = {
        k: v for k, v in os.environ.items() if k != "XLA_FLAGS"
    }
    env["JAX_PLATFORMS"] = "cpu"
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", WORKER, addr, str(pid), REPO],
            stdout=subprocess.PIPE,
            stderr=subprocess.PIPE,
            text=True,
            env=env,
        )
        for pid in (0, 1)
    ]
    outs = []
    for p in procs:
        try:
            out, err = p.communicate(timeout=150)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail("multi-host worker timed out")
        outs.append((p.returncode, out, err))
    for rc, out, err in outs:
        assert rc == 0, f"worker failed:\n{out}\n{err}"
    assert any("OK process=0" in o for _, o, _ in outs)
    assert any("OK process=1" in o for _, o, _ in outs)


def test_two_process_training_matches_single_process(tmp_path):
    """Multi-host DP *training* end to end [SURVEY.md 3.4: the reference's
    master/slave actually trained across processes — job loop, loader shard
    assignment, aggregation]: 2 processes, each feeding only its half of
    every global minibatch, must reproduce the single-process loss
    trajectory; only the coordinator writes snapshots."""
    import json

    import numpy as np

    addr = f"127.0.0.1:{_free_port()}"
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    snap_root = str(tmp_path)
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", TRAIN_WORKER, addr, str(pid), snap_root, REPO],
            stdout=subprocess.PIPE,
            stderr=subprocess.PIPE,
            text=True,
            env=env,
        )
        for pid in (0, 1)
    ]
    outs = []
    for p in procs:
        try:
            out, err = p.communicate(timeout=300)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail("multi-host training worker timed out")
        outs.append((p.returncode, out, err))
    for rc, out, err in outs:
        assert rc == 0, f"worker failed:\n{out}\n{err}"

    hists = {}
    for _, out, _ in outs:
        for line in out.splitlines():
            if line.startswith("HIST"):
                pid, _, payload = line[4:].partition("=")
                hists[int(pid)] = json.loads(payload)
    assert set(hists) == {0, 1}
    # both processes observed the SAME global metrics (no per-process drift)
    assert hists[0] == hists[1]

    # single-process baseline, same seeds (DP == single-device is proven by
    # tests/test_parallel.py; here cross-PROCESS must match too)
    from znicz_tpu.core import prng
    from znicz_tpu.loader import datasets
    from znicz_tpu.workflow import StandardWorkflow

    prng.seed_all(99)
    loader = datasets.mnist(n_train=256, n_test=64, minibatch_size=64)
    wf = StandardWorkflow(
        loader,
        [
            {"type": "all2all_tanh", "->": {"output_sample_shape": 32}},
            {"type": "softmax", "->": {"output_sample_shape": 10}},
        ],
        decision_config={"max_epochs": 3},
        default_hyper={"learning_rate": 0.1, "gradient_moment": 0.9},
    )
    wf.initialize(seed=99)
    dec = wf.run()
    assert len(dec.history) == len(hists[0])
    for es, ep in zip(dec.history, hists[0]):
        assert es["train"]["n_err"] == ep["train_n_err"]
        assert es["test"]["n_err"] == ep["test_n_err"]
        np.testing.assert_allclose(
            es["train"]["loss"], ep["train_loss"], rtol=1e-4
        )

    # coordinator-gated snapshots: proc0's dir has them, proc1's is empty
    wrote0 = os.listdir(tmp_path / "proc0")
    wrote1 = (
        os.listdir(tmp_path / "proc1")
        if os.path.isdir(tmp_path / "proc1")
        else []
    )
    assert any(f.startswith("workflow") for f in wrote0), wrote0
    assert wrote1 == [], wrote1


def test_two_process_tensor_parallel_conv_training(tmp_path):
    """Multi-host x TP x conv (VERDICT r3 weak #7): 2 processes x 2 local
    devices on a (data=2, model=2) mesh — conv kernels shard over model
    ACROSS hosts, exercising shard_state's numpy round-trip and the
    snapshotter's cross-host allgather under real multi-process training.
    Losses must match the single-process 4-device run."""
    import json

    import numpy as np

    addr = f"127.0.0.1:{_free_port()}"
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    snap_dir = str(tmp_path / "snaps")
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", TP_CONV_WORKER, addr, str(pid), snap_dir,
             REPO],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env,
        )
        for pid in (0, 1)
    ]
    outs = []
    for p in procs:
        try:
            out, err = p.communicate(timeout=300)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail("tp conv worker timed out")
        outs.append((p.returncode, out, err))
    for rc, out, err in outs:
        assert rc == 0, f"worker failed:\n{out}\n{err}"
    hists = {}
    for _, out, _ in outs:
        for line in out.splitlines():
            if line.startswith("HIST"):
                pid, _, payload = line[4:].partition("=")
                hists[int(pid)] = json.loads(payload)
    assert set(hists) == {0, 1}
    assert hists[0] == hists[1]
    # the coordinator's snapshot contains the ALLGATHERED full conv kernel
    from znicz_tpu.workflow.snapshotter import load_snapshot

    state, host = load_snapshot(
        os.path.join(snap_dir, "workflow_epoch2.pickle.gz")
    )
    assert np.asarray(state[0][0]["weights"]).shape == (5, 5, 1, 8)

    # single-process baseline on a 4-device (data=2, model=2) mesh
    import jax

    from znicz_tpu.core import prng
    from znicz_tpu.loader import datasets
    from znicz_tpu.parallel import DataParallel, make_mesh
    from znicz_tpu.workflow import StandardWorkflow

    prng.seed_all(55)
    loader = datasets.mnist(
        n_train=128, n_test=0, minibatch_size=32, flat=False
    )
    wf = StandardWorkflow(
        loader,
        [
            {"type": "conv_relu", "->": {"n_kernels": 8, "kx": 5, "ky": 5}},
            {"type": "max_pooling", "->": {"kx": 2, "ky": 2}},
            {"type": "conv_relu", "->": {"n_kernels": 16, "kx": 5, "ky": 5}},
            {"type": "max_pooling", "->": {"kx": 2, "ky": 2}},
            {"type": "softmax", "->": {"output_sample_shape": 10}},
        ],
        decision_config={"max_epochs": 3},
        default_hyper={"learning_rate": 0.05, "gradient_moment": 0.9},
        parallel=DataParallel(
            make_mesh(2, 2, devices=jax.devices()[:4]), tp=True
        ),
    )
    wf.initialize(seed=55)
    base = [e["train"]["loss"] for e in wf.run().history]
    np.testing.assert_allclose(base, hists[0], rtol=1e-4)


def test_two_process_pool_sharded_training(tmp_path):
    """Multi-host x data-axis-sharded HBM pool: each process device_puts
    ONLY its shards' rows (the capacity contract that lets the pooled
    dataset exceed any one host/chip), assembled globally via
    make_array_from_process_local_data; losses must match the
    single-process 4-device pool-sharded run."""
    import json

    import numpy as np

    addr = f"127.0.0.1:{_free_port()}"
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", POOL_SHARDED_WORKER, addr, str(pid), REPO],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env,
        )
        for pid in (0, 1)
    ]
    outs = []
    for p in procs:
        try:
            out, err = p.communicate(timeout=300)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail("pool-sharded worker timed out")
        outs.append((p.returncode, out, err))
    for rc, out, err in outs:
        assert rc == 0, f"worker failed:\n{out}\n{err}"
    hists = {}
    for _, out, _ in outs:
        for line in out.splitlines():
            if line.startswith("HIST"):
                pid, _, payload = line[4:].partition("=")
                hists[int(pid)] = json.loads(payload)
    assert set(hists) == {0, 1}
    assert hists[0] == hists[1]

    # single-process baseline: same config on a 4-device mesh
    import jax

    from znicz_tpu.core import prng
    from znicz_tpu.loader import FullBatchLoader
    from znicz_tpu.parallel import DataParallel, make_mesh
    from znicz_tpu.workflow import StandardWorkflow

    gen = np.random.default_rng(3)
    imgs = gen.integers(0, 256, (128, 8, 8, 1), dtype=np.uint8)
    labels = (imgs.mean(axis=(1, 2, 3)) > 127).astype(np.int32)
    prng.seed_all(67)
    loader = FullBatchLoader(
        {"train": imgs}, {"train": labels}, minibatch_size=32,
        normalization="range",
        normalization_kwargs={"scale": 255.0, "shift": -0.5},
        device_resident=True, pool_sharded=True,
    )
    wf = StandardWorkflow(
        loader,
        [{"type": "all2all_tanh", "->": {"output_sample_shape": 8}},
         {"type": "softmax", "->": {"output_sample_shape": 2}}],
        decision_config={"max_epochs": 3},
        default_hyper={"learning_rate": 0.1, "gradient_moment": 0.9},
        parallel=DataParallel(make_mesh(4, 1, devices=jax.devices()[:4])),
    )
    wf.initialize(seed=67)
    base = [e["train"]["loss"] for e in wf.run().history]
    np.testing.assert_allclose(base, hists[0], rtol=1e-4)


def test_kill_and_resume_from_coordinator_snapshot(tmp_path):
    """Elastic failure recovery, demonstrated (VERDICT r3 missing #1): a
    2-process job loses one process to SIGKILL mid-training; both restart
    from the coordinator's latest durable snapshot and the final loss
    trajectory matches an uninterrupted run — the checkpoint-restart
    counterpart of the reference master's ``drop_slave`` re-queue
    [SURVEY.md 5.3]."""
    import json
    import signal
    import time as _time

    import numpy as np

    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    snap_dir = str(tmp_path / "snaps")

    # ---- phase 1: train, SIGKILL process 1 after epoch 2's snapshot
    addr = f"127.0.0.1:{_free_port()}"
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", KILL_WORKER, addr, str(pid), snap_dir,
             REPO, "kill"],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env,
        )
        for pid in (0, 1)
    ]
    # the launcher-as-supervisor role: once a worker dies, tear the job
    # down (the surviving process is blocked in a collective)
    deadline = _time.time() + 300
    while _time.time() < deadline:
        if procs[1].poll() is not None:
            break
        _time.sleep(0.5)
    else:
        for p in procs:
            p.kill()
        pytest.fail("process 1 never died")
    assert procs[1].returncode == -signal.SIGKILL
    _time.sleep(2.0)  # let proc0 finish any in-flight snapshot write
    procs[0].kill()
    procs[0].communicate()
    procs[1].communicate()

    # durable state: the coordinator wrote periodic snapshots up to epoch 2
    snaps = sorted(os.listdir(snap_dir))
    assert "workflow_epoch2.pickle.gz" in snaps, snaps

    # ---- phase 2: both processes restart from the epoch-2 snapshot
    addr2 = f"127.0.0.1:{_free_port()}"
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", KILL_WORKER, addr2, str(pid), snap_dir,
             REPO, "resume"],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env,
        )
        for pid in (0, 1)
    ]
    outs = []
    for p in procs:
        try:
            out, err = p.communicate(timeout=300)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail("resume worker timed out")
        outs.append((p.returncode, out, err))
    for rc, out, err in outs:
        assert rc == 0, f"resume worker failed:\n{out}\n{err}"
    hists = {}
    for _, out, _ in outs:
        for line in out.splitlines():
            if line.startswith("HIST"):
                pid, _, payload = line[4:].partition("=")
                hists[int(pid)] = json.loads(payload)
    assert set(hists) == {0, 1}
    assert hists[0] == hists[1]
    # restored history (epochs 0-2) + resumed epochs (3-4) = full run
    assert len(hists[0]) == 5

    # ---- uninterrupted single-process baseline, same seeds
    from znicz_tpu.core import prng
    from znicz_tpu.loader import datasets
    from znicz_tpu.workflow import StandardWorkflow

    prng.seed_all(99)
    loader = datasets.mnist(n_train=256, n_test=64, minibatch_size=64)
    wf = StandardWorkflow(
        loader,
        [
            {"type": "all2all_tanh", "->": {"output_sample_shape": 32}},
            {"type": "softmax", "->": {"output_sample_shape": 10}},
        ],
        decision_config={"max_epochs": 5},
        default_hyper={"learning_rate": 0.1, "gradient_moment": 0.9},
    )
    wf.initialize(seed=99)
    dec = wf.run()
    assert len(dec.history) == 5
    for es, ep in zip(dec.history, hists[0]):
        assert es["train"]["n_err"] == ep["train_n_err"]
        np.testing.assert_allclose(
            es["train"]["loss"], ep["train_loss"], rtol=1e-4
        )


def test_two_process_device_resident_scan_training(tmp_path):
    """Multi-host x device-resident x scanned dispatch: the HBM pool is
    replicated per process, each process stacks only ITS loader shard, and
    the whole-split lax.scan runs over global arrays — losses must match
    the single-process run of the identical config."""
    import json

    import numpy as np

    addr = f"127.0.0.1:{_free_port()}"
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", SCAN_WORKER, addr, str(pid), REPO],
            stdout=subprocess.PIPE,
            stderr=subprocess.PIPE,
            text=True,
            env=env,
        )
        for pid in (0, 1)
    ]
    outs = []
    for p in procs:
        try:
            out, err = p.communicate(timeout=300)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail("multi-host scan worker timed out")
        outs.append((p.returncode, out, err))
    for rc, out, err in outs:
        assert rc == 0, f"worker failed:\n{out}\n{err}"
    hists = {}
    for _, out, _ in outs:
        for line in out.splitlines():
            if line.startswith("HIST"):
                pid, _, payload = line[4:].partition("=")
                hists[int(pid)] = json.loads(payload)
    assert hists[0] == hists[1]

    # single-process baseline of the same config
    from znicz_tpu.core import prng
    from znicz_tpu.loader import FullBatchLoader
    from znicz_tpu.workflow import StandardWorkflow

    gen = np.random.default_rng(0)
    imgs = gen.integers(0, 256, (256, 64), dtype=np.uint8)
    labels = gen.integers(0, 10, 256).astype(np.int32)
    prng.seed_all(77)
    loader = FullBatchLoader(
        {"train": imgs}, {"train": labels}, minibatch_size=64,
        normalization="range",
        normalization_kwargs={"scale": 255.0, "shift": -0.5},
        device_resident=True,
    )
    wf = StandardWorkflow(
        loader,
        [
            {"type": "all2all_tanh", "->": {"output_sample_shape": 32}},
            {"type": "softmax", "->": {"output_sample_shape": 10}},
        ],
        decision_config={"max_epochs": 3},
        default_hyper={"learning_rate": 0.1, "gradient_moment": 0.9},
    )
    wf.initialize(seed=77)
    base = [e["train"]["loss"] for e in wf.run().history]
    np.testing.assert_allclose(base, hists[0], rtol=1e-4)
