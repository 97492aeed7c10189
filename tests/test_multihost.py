"""Real multi-process distributed training: two OS processes rendezvous via
``jax.distributed`` (the torchrun-contract path, parallel/mesh.py
setup_distributed), build one global mesh over 2x4 virtual CPU devices, and
take lockstep data-parallel train steps on host-local batch halves.

This exercises what the in-process 8-device tests cannot: coordinator
rendezvous, ``jax.make_array_from_process_local_data`` with process-local
rows, cross-process collectives in the jitted step, and identical global
metrics on every host (SURVEY.md §2d — the NCCL/torchrun analog surface).
"""

import os
import subprocess
import sys

import numpy as np
import pytest


_WORKER = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")
sys.path.insert(0, os.environ["REPO"])

from distributed_training_pytorch_tpu.parallel import mesh as mesh_lib

mesh_lib.setup_distributed(
    coordinator_address=os.environ["COORD"],
    num_processes=2,
    process_id=int(os.environ["PID_IDX"]),
)
assert jax.process_count() == 2, jax.process_count()
assert len(jax.devices()) == 8, len(jax.devices())  # 2 hosts x 4 local

import jax.numpy as jnp, numpy as np, optax
from distributed_training_pytorch_tpu.ops import cross_entropy_loss
from distributed_training_pytorch_tpu.train import TrainEngine, make_supervised_loss
from flax import linen as nn

class MLP(nn.Module):
    @nn.compact
    def __call__(self, x, *, train=False):
        x = x.reshape(x.shape[0], -1)
        return nn.Dense(3)(nn.relu(nn.Dense(16)(x)))

model = MLP()
def criterion(logits, b):
    loss = cross_entropy_loss(logits, b["label"])
    return loss, {"loss": loss}

mesh = mesh_lib.create_mesh()  # 1-D data mesh over all 8 global devices
engine = TrainEngine(make_supervised_loss(model, criterion), optax.sgd(0.05), mesh)
state = engine.init_state(jax.random.key(0), lambda r: model.init(r, jnp.zeros((1, 4))))

# Each process contributes ITS half of the global batch (global-batch
# semantics: 16 rows total, 8 local — trainer/trainer.py:56 analog).
pid = jax.process_index()
rng = np.random.RandomState(42)  # same stream everywhere; slice per process
x = rng.randn(16, 4).astype(np.float32)
y = rng.randint(0, 3, size=(16,)).astype(np.int32)
local = slice(pid * 8, (pid + 1) * 8)
batch = engine.shard_batch({"image": x[local], "label": y[local]})

losses = []
for _ in range(5):
    state, m = engine.train_step(state, batch)
    losses.append(float(m["loss"]))
print(f"RESULT {jax.process_index()} " + " ".join(f"{l:.6f}" for l in losses), flush=True)
mesh_lib.shutdown_distributed()
"""


@pytest.mark.slow  # soak-shaped: moved out of tier-1 to keep it inside its cap (PR 21)
@pytest.mark.skipif(os.name != "posix", reason="subprocess workers")
def test_two_process_distributed_train(tmp_path):
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = tmp_path / "worker.py"
    script.write_text(_WORKER)
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    procs = []
    outs = []
    try:
        for pid in range(2):
            env = dict(
                os.environ,
                REPO=repo,
                COORD=f"127.0.0.1:{port}",
                PID_IDX=str(pid),
            )
            env.pop("JAX_PLATFORMS", None)
            procs.append(
                subprocess.Popen(
                    [sys.executable, str(script)],
                    env=env,
                    stdout=subprocess.PIPE,
                    stderr=subprocess.STDOUT,
                    text=True,
                )
            )
        for p in procs:
            out, _ = p.communicate(timeout=240)
            outs.append(out)
    finally:
        # A hung rendezvous or early failure must not orphan the peer:
        # it would block in jax.distributed forever, pinning the port.
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
    for p, out in zip(procs, outs, strict=True):
        assert p.returncode == 0, out[-3000:]
    results = {}
    for out in outs:
        for line in out.splitlines():
            if line.startswith("RESULT"):
                _, pid, *vals = line.split()
                results[int(pid)] = [float(v) for v in vals]
    assert set(results) == {0, 1}, outs
    # Global metrics must be identical on both hosts, and training must move.
    np.testing.assert_allclose(results[0], results[1], rtol=1e-6)
    assert results[0][-1] < results[0][0]


_TRAINER_WORKER = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")
sys.path.insert(0, os.environ["REPO"])

from distributed_training_pytorch_tpu.parallel import mesh as mesh_lib

mesh_lib.setup_distributed(
    coordinator_address=os.environ["COORD"],
    num_processes=2,
    process_id=int(os.environ["PID_IDX"]),
)

import jax.numpy as jnp, numpy as np, optax
from distributed_training_pytorch_tpu.data import ArrayDataSource
from distributed_training_pytorch_tpu.ops import accuracy, cross_entropy_loss, multistep_lr
from distributed_training_pytorch_tpu.trainer import Trainer
from distributed_training_pytorch_tpu.utils import Logger
from flax import linen as nn

SAVE = os.environ["SAVE_DIR"]
pid = jax.process_index()

class MLP(nn.Module):
    @nn.compact
    def __call__(self, x, *, train=False):
        x = x.reshape(x.shape[0], -1)
        return nn.Dense(3)(nn.relu(nn.Dense(16)(x)))

def synth(n, seed):
    rng = np.random.RandomState(seed)
    y = rng.randint(0, 3, size=(n,)).astype(np.int32)
    x = (rng.randn(n, 4, 4, 3) + y[:, None, None, None]).astype(np.float32)
    return x, y

class TwoProcTrainer(Trainer):
    preempt_after_epoch = None  # set on ONE process; the vote must stop BOTH

    def build_train_dataset(self):
        x, y = synth(48, 0)   # same global arrays on every host; the
        return ArrayDataSource(image=x, label=y)  # loader slices per process

    def build_val_dataset(self):
        x, y = synth(24, 1)
        return ArrayDataSource(image=x, label=y)

    def build_model(self):
        return MLP()

    criterion_uses_mask = True

    def build_criterion(self):
        def criterion(logits, batch):
            mask = batch.get("mask")
            loss = cross_entropy_loss(logits, batch["label"], weights=mask)
            return loss, {"ce_loss": loss,
                          "accuracy": accuracy(logits, batch["label"], weights=mask)}
        return criterion

    def build_optimizer(self, schedule):
        return optax.sgd(schedule, momentum=0.9)

    def build_scheduler(self):
        return multistep_lr(0.05, milestones=[50], steps_per_epoch=3)

    def train_epoch(self, epoch):
        m = super().train_epoch(epoch)
        if self.preempt_after_epoch is not None and epoch == self.preempt_after_epoch:
            self._preempted = True  # simulates SIGTERM landing on this host
        return m

def make(snapshot=None, preempt_on=None, max_epoch=4):
    t = TwoProcTrainer(
        max_epoch=max_epoch,
        batch_size=16,            # global; 8 rows per process
        have_validate=True,
        save_best_for=("accuracy", "geq"),
        save_period=2,
        save_folder=SAVE,
        snapshot_path=snapshot,
        logger=Logger("twoproc", os.path.join(SAVE, "logfile.log")),
        progress=False,
        async_checkpoint=False,
        preemption_check_every=1,
    )
    if preempt_on is not None and pid == preempt_on:
        t.preempt_after_epoch = 1
    return t

# Phase 1: train with a simulated preemption signal on process 1 only after
# epoch 1 — the collective vote must stop BOTH processes at the same epoch
# and save a resumable snapshot.
t = make(preempt_on=1)
t.train()
assert t._preempted, "collective preemption vote must reach every host"
assert t.cur_epoch == 1, t.cur_epoch
last = os.path.join(SAVE, "weights", "last")
assert os.path.isdir(last), "preemption must leave a resumable snapshot"

# Phase 2: resume from the snapshot and run to completion (validation each
# save_period, best/last checkpointing through collective Orbax saves).
t2 = make(snapshot=last)
t2.train()
assert not t2._preempted
assert t2.cur_epoch == 3, t2.cur_epoch
m = t2.validate()
p0 = float(jax.tree.leaves(t2.state.params)[0].sum())
print(f"RESULT {pid} {int(t2.state.step)} {m['accuracy']:.6f} {m['ce_loss']:.6f} {p0:.6f}", flush=True)
mesh_lib.shutdown_distributed()
"""


@pytest.mark.skipif(os.name != "posix", reason="subprocess workers")
@pytest.mark.slow
def test_two_process_full_trainer(tmp_path):
    """Full Trainer.train() across 2 real processes: loader sharding,
    collective validation, collective checkpoint saves, the preemption vote
    stopping BOTH hosts, and snapshot resume — the path run.sh runs on a
    pod."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = tmp_path / "trainer_worker.py"
    script.write_text(_TRAINER_WORKER)
    save_dir = tmp_path / "shared"
    save_dir.mkdir()
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    procs, outs = [], []
    try:
        for pid in range(2):
            env = dict(
                os.environ,
                REPO=repo,
                COORD=f"127.0.0.1:{port}",
                PID_IDX=str(pid),
                SAVE_DIR=str(save_dir),
            )
            env.pop("JAX_PLATFORMS", None)
            procs.append(
                subprocess.Popen(
                    [sys.executable, str(script)],
                    env=env,
                    stdout=subprocess.PIPE,
                    stderr=subprocess.STDOUT,
                    text=True,
                )
            )
        for p in procs:
            out, _ = p.communicate(timeout=420)
            outs.append(out)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
    for p, out in zip(procs, outs, strict=True):
        assert p.returncode == 0, out[-4000:]
    results = {}
    for out in outs:
        for line in out.splitlines():
            if line.startswith("RESULT"):
                _, pid, step, *vals = line.split()
                results[int(pid)] = (int(step), [float(v) for v in vals])
    assert set(results) == {0, 1}, outs
    # Same step count, identical global metrics and params on both hosts.
    assert results[0][0] == results[1][0]
    np.testing.assert_allclose(results[0][1], results[1][1], rtol=1e-6)
    # best/last checkpoints exist in the shared folder
    assert (save_dir / "weights" / "last").is_dir()
    assert (save_dir / "weights" / "best").is_dir()


_MP_WORKER = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=" + os.environ["LOCAL_DEVS"]
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")
sys.path.insert(0, os.environ["REPO"])

from distributed_training_pytorch_tpu.parallel import mesh as mesh_lib

MODE = os.environ["MODE"]
if MODE == "train":
    mesh_lib.setup_distributed(
        coordinator_address=os.environ["COORD"],
        num_processes=2,
        process_id=int(os.environ["PID_IDX"]),
    )
    assert jax.process_count() == 2 and len(jax.devices()) == 8

import jax.numpy as jnp, numpy as np, optax
from distributed_training_pytorch_tpu.checkpoint import CheckpointManager
from distributed_training_pytorch_tpu.models import ViTTiny
from distributed_training_pytorch_tpu.ops import cross_entropy_loss
from distributed_training_pytorch_tpu.parallel.sharding import transformer_tp_rules
from distributed_training_pytorch_tpu.train import TrainEngine, make_supervised_loss

SAVE = os.environ["SAVE_DIR"]
model = ViTTiny(num_classes=3)

def criterion(logits, b):
    loss = cross_entropy_loss(logits, b["label"])
    return loss, {"loss": loss}

def build(mesh, rules=None, min_size=2**18):
    engine = TrainEngine(
        make_supervised_loss(model, criterion), optax.sgd(0.05, momentum=0.9),
        mesh, sharding_rules=rules, fsdp_min_size=min_size,
    )
    state = engine.init_state(
        jax.random.key(0), lambda r: model.init(r, jnp.zeros((1, 16, 16, 3)))
    )
    return engine, state

rng = np.random.RandomState(42)
X = rng.randn(16, 16, 16, 3).astype(np.float32)
Y = rng.randint(0, 3, size=(16,)).astype(np.int32)

def steps(engine, state, local):
    batch = engine.shard_batch({"image": X[local], "label": Y[local]})
    losses = []
    for _ in range(2):
        state, m = engine.train_step(state, batch)
        losses.append(float(m["loss"]))
    return state, losses

def fingerprint(state):
    # replicated leaf-sums via a (possibly cross-process) jitted reduction
    sums = jax.jit(lambda p: [jnp.sum(jnp.abs(x).astype(jnp.float32)) for x in jax.tree.leaves(p)])(state.params)
    return [float(s) for s in sums[:4]] + [float(sum(float(s) for s in sums))]

if MODE == "train":
    pid = jax.process_index()
    local = slice(pid * 8, (pid + 1) * 8)

    # (a) reference: pure DP over all 8 devices (2 processes)
    eng_dp, st_dp = build(mesh_lib.create_mesh({mesh_lib.DATA_AXIS: 8}))
    _, losses_dp = steps(eng_dp, st_dp, local)

    # (b) fsdp axis SPANS the process boundary (fsdp=2 outermost over 2x4
    # devices), tensor-parallel within each process
    mesh_ft = mesh_lib.create_mesh({mesh_lib.FSDP_AXIS: 2, mesh_lib.TENSOR_AXIS: 4})
    eng_ft, st_ft = build(mesh_ft, rules=transformer_tp_rules(), min_size=1024)
    st_ft_trained, losses_ft = steps(eng_ft, st_ft, local)

    # (c) pure TP over all 8 devices: the tensor axis itself crosses the
    # process boundary; batch is replicated so each process feeds all rows
    eng_tp, st_tp = build(mesh_lib.create_mesh({mesh_lib.TENSOR_AXIS: 8}),
                          rules=transformer_tp_rules())
    _, losses_tp = steps(eng_tp, st_tp, slice(None))

    # collective sharded save of the cross-process fsdp+tp state
    mgr = CheckpointManager(SAVE, async_save=False)
    mgr.save("last", st_ft_trained, epoch=2)
    mgr.close()
    fp = fingerprint(st_ft_trained)
    vals = losses_dp + losses_ft + losses_tp + fp
    print(f"RESULT {pid} " + " ".join(f"{v:.6f}" for v in vals), flush=True)
    mesh_lib.shutdown_distributed()
else:
    # restore the 2-process sharded checkpoint in ONE process on a smaller
    # mesh — process-count AND topology change in one restore
    mesh = mesh_lib.create_mesh(
        {mesh_lib.FSDP_AXIS: 2, mesh_lib.TENSOR_AXIS: 2}, devices=jax.devices()[:4]
    )
    engine, target = build(mesh, rules=transformer_tp_rules(), min_size=1024)
    mgr = CheckpointManager(SAVE, async_save=False)
    restored, epoch = mgr.restore("last", target)
    mgr.close()
    assert epoch == 2 and int(restored.step) == 2
    fp = fingerprint(restored)
    print("RESULT R " + " ".join(f"{v:.6f}" for v in fp), flush=True)
"""


@pytest.mark.skipif(os.name != "posix", reason="subprocess workers")
@pytest.mark.slow
def test_cross_process_model_parallel_and_sharded_restore(tmp_path):
    """Model-parallel axes across a REAL process boundary: (a) DP reference, (b) fsdp spanning the 2 processes + in-process TP,
    (c) a tensor axis itself spanning the boundary — all three loss
    trajectories must agree; then the cross-process fsdp+tp-sharded TrainState
    saves collectively and restores into a SINGLE process on a smaller mesh
    with identical params."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = tmp_path / "mp_worker.py"
    script.write_text(_MP_WORKER)
    save_dir = tmp_path / "shared"
    save_dir.mkdir()
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    procs, outs = [], []
    base = dict(os.environ, REPO=repo, SAVE_DIR=str(save_dir))
    base.pop("JAX_PLATFORMS", None)
    try:
        for pid in range(2):
            env = dict(
                base, COORD=f"127.0.0.1:{port}", PID_IDX=str(pid),
                MODE="train", LOCAL_DEVS="4",
            )
            procs.append(
                subprocess.Popen(
                    [sys.executable, str(script)], env=env,
                    stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
                )
            )
        for p in procs:
            out, _ = p.communicate(timeout=420)
            outs.append(out)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
    for p, out in zip(procs, outs, strict=True):
        assert p.returncode == 0, out[-4000:]
    results = {}
    for out in outs:
        for line in out.splitlines():
            if line.startswith("RESULT"):
                _, pid, *vals = line.split()
                results[pid] = [float(v) for v in vals]
    assert set(results) == {"0", "1"}, outs
    np.testing.assert_allclose(results["0"], results["1"], rtol=1e-6)
    losses_dp, losses_ft, losses_tp = (
        results["0"][0:2], results["0"][2:4], results["0"][4:6]
    )
    # cross-process fsdp+tp and cross-process pure-TP match the DP reference
    np.testing.assert_allclose(losses_ft, losses_dp, rtol=2e-4)
    np.testing.assert_allclose(losses_tp, losses_dp, rtol=2e-4)

    # single-process restore of the 2-process sharded checkpoint
    out = subprocess.run(
        [sys.executable, str(script)],
        env=dict(base, MODE="restore", LOCAL_DEVS="8"),
        capture_output=True, text=True, timeout=420,
    )
    assert out.returncode == 0, out.stdout[-4000:] + out.stderr[-2000:]
    fp = None
    for line in out.stdout.splitlines():
        if line.startswith("RESULT R"):
            fp = [float(v) for v in line.split()[2:]]
    assert fp is not None, out.stdout
    np.testing.assert_allclose(fp, results["0"][6:], rtol=1e-5)
