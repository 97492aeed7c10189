"""Checkpoint round-trip + best/last/periodic policy tests (SURVEY.md §4)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from distributed_training_pytorch_tpu.checkpoint import (
    BEST,
    LAST,
    CheckpointManager,
    epoch_checkpoint_name,
)
from distributed_training_pytorch_tpu.models import VGG16
from distributed_training_pytorch_tpu.parallel import mesh as mesh_lib
from distributed_training_pytorch_tpu.train import TrainEngine, make_supervised_loss
from distributed_training_pytorch_tpu.ops import cross_entropy_loss


def _small_state(devices, seed=0):
    mesh = mesh_lib.create_mesh({mesh_lib.DATA_AXIS: len(devices)}, devices=devices)
    model = VGG16(
        num_classes=3, stage_features=(4, 8), stage_layers=(1, 1), classifier_widths=(16,)
    )

    def criterion(logits, batch):
        loss = cross_entropy_loss(logits, batch["label"])
        return loss, {"loss": loss}

    engine = TrainEngine(
        make_supervised_loss(model, criterion), optax.sgd(0.01, momentum=0.9), mesh
    )
    state = engine.init_state(
        jax.random.key(seed), lambda rng: model.init(rng, jnp.zeros((1, 16, 16, 3)))
    )
    return engine, state


@pytest.fixture(scope="module")
def shared(devices):
    """(engine, state, differently-seeded state) built once — each init pays a
    multi-second jit compile on the CPU test platform. Managers only read the
    states (saves copy, restores return new pytrees), so sharing is safe."""
    engine, state = _small_state(devices, seed=0)
    _, other = _small_state(devices, seed=1)
    return engine, state, other


def test_round_trip(tmp_path, shared):
    engine, state, other = shared
    mgr = CheckpointManager(tmp_path / "ckpt", async_save=False)
    mgr.save(LAST, state, epoch=7)
    assert mgr.exists(LAST)

    # Restore into a differently-seeded state; values must match the saved one.
    restored, epoch = mgr.restore(LAST, other)
    assert epoch == 7
    leaves_a = jax.tree.leaves(state.params)
    leaves_b = jax.tree.leaves(restored.params)
    for a, b in zip(leaves_a, leaves_b, strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # opt_state (momentum buffers) round-trips too.
    for a, b in zip(jax.tree.leaves(state.opt_state), jax.tree.leaves(restored.opt_state), strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    mgr.close()


def test_best_policy_geq(tmp_path, shared):
    _, state, _ = shared
    mgr = CheckpointManager(
        tmp_path / "ckpt", save_best_for=("accuracy", "geq"), async_save=False
    )
    assert mgr.maybe_save_best({"accuracy": 0.5}, state, epoch=0)
    assert mgr.best_value == 0.5
    assert not mgr.maybe_save_best({"accuracy": 0.4}, state, epoch=5)
    assert mgr.best_value == 0.5
    # geq: equal counts as improvement (trainer/trainer.py:119 semantics).
    assert mgr.maybe_save_best({"accuracy": 0.5}, state, epoch=10)
    assert mgr.maybe_save_best({"accuracy": 0.9}, state, epoch=15)
    assert mgr.exists(BEST)
    _, epoch = mgr.restore(BEST, state)
    assert epoch == 15
    assert mgr.best_value == 0.9
    mgr.close()


def test_best_policy_leq(tmp_path, shared):
    _, state, _ = shared
    mgr = CheckpointManager(tmp_path / "c", save_best_for=("loss", "leq"), async_save=False)
    assert mgr.maybe_save_best({"loss": 1.0}, state, epoch=0)
    assert not mgr.maybe_save_best({"loss": 2.0}, state, epoch=1)
    assert mgr.maybe_save_best({"loss": 0.5}, state, epoch=2)
    mgr.close()


def test_best_value_survives_restore(tmp_path, shared):
    _, state, _ = shared
    mgr = CheckpointManager(tmp_path / "c", save_best_for=("accuracy", "geq"), async_save=False)
    mgr.maybe_save_best({"accuracy": 0.8}, state, epoch=3)
    mgr.close()
    # Fresh manager (new process analog): best threshold recovers from meta.
    mgr2 = CheckpointManager(tmp_path / "c", save_best_for=("accuracy", "geq"), async_save=False)
    mgr2.restore(BEST, state)
    assert mgr2.best_value == 0.8
    assert not mgr2.maybe_save_best({"accuracy": 0.7}, state, epoch=4)
    mgr2.close()


def test_epoch_name_and_missing(tmp_path, shared):
    _, state, _ = shared
    assert epoch_checkpoint_name(40) == "checkpoint_epoch_40"
    mgr = CheckpointManager(tmp_path / "c", async_save=False)
    with pytest.raises(FileNotFoundError):
        mgr.restore("nope", state)
    mgr.close()


def test_async_save_overwrite(tmp_path, shared):
    engine, state, _ = shared
    mgr = CheckpointManager(tmp_path / "c", async_save=True)
    mgr.save(LAST, state, epoch=1)
    mgr.save(LAST, state, epoch=2)  # overwrites; must wait for in-flight save
    restored, epoch = mgr.restore(LAST, state)
    assert epoch == 2
    mgr.close()


def test_logger(tmp_path, capsys):
    from distributed_training_pytorch_tpu.utils import Logger

    log_file = tmp_path / "runs" / "logfile.log"
    logger = Logger("VGG16", str(log_file))
    logger.log("hello", "info")
    logger.log("watch out", "warning")
    logger.log("boom", "error")
    logger.log("default path", "anything-else")  # maps to info (utils/logger.py:33)
    out = capsys.readouterr().out
    assert "hello" in out and "watch out" in out and "boom" in out
    content = log_file.read_text()
    assert "hello" in content and "WARNING" in content and "ERROR" in content
    assert "default path" in content


def test_max_to_keep_prunes_periodic_only(tmp_path, shared):
    """Retention keeps the newest N checkpoint_epoch_* and never touches
    best/last."""
    _, state, _ = shared
    mgr = CheckpointManager(tmp_path / "c", async_save=False, max_to_keep=2)
    for ep in (1, 2, 3, 4):
        mgr.save(epoch_checkpoint_name(ep), state, epoch=ep)
    mgr.save(LAST, state, epoch=5)  # triggers gc of committed periodics
    mgr.close()
    kept = sorted(p.name for p in (tmp_path / "c").iterdir())
    assert "last" in kept
    assert "checkpoint_epoch_4" in kept and "checkpoint_epoch_3" in kept
    assert "checkpoint_epoch_1" not in kept and "checkpoint_epoch_2" not in kept


def test_params_only_restore_across_prng_impls(tmp_path, shared):
    """A checkpoint saved by an rbg-keyed training run must restore
    params_only into a threefry-keyed eval process (key widths differ: 4 vs 2
    words) — regression for the eval_lm cross-impl failure."""
    from distributed_training_pytorch_tpu.train import TrainState

    _, state, _ = shared
    rbg_state = state.replace(rng=jax.random.key(0, impl="rbg"))
    mgr = CheckpointManager(tmp_path / "c", async_save=False)
    mgr.save("last", rbg_state, epoch=3)

    target = TrainState(
        step=jnp.zeros((), jnp.int32),
        params=jax.tree.map(jnp.zeros_like, state.params),
        opt_state=(),
        model_state={},
        rng=jax.random.key(0),  # default threefry (2 words)
    )
    restored, epoch = mgr.restore("last", target, params_only=True)
    assert epoch == 3
    for a, b in zip(jax.tree.leaves(state.params), jax.tree.leaves(restored.params), strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    mgr.close()


# ---------------------------------------------------------------------------
# Sharded-state checkpointing: FSDP+TP-sharded TrainState
# round-trips, including onto a DIFFERENT mesh topology — the pod-scale resume
# capability (ref trainer/trainer.py:96-101 once params are sharded).


def _vit_engine(devices, axes, *, rules=None, min_size=2**18, seed=0, steps=0):
    from distributed_training_pytorch_tpu.models import ViTTiny

    mesh = mesh_lib.create_mesh(axes, devices=devices)
    model = ViTTiny(num_classes=4)

    def criterion(logits, batch):
        loss = cross_entropy_loss(logits, batch["label"])
        return loss, {"loss": loss}

    engine = TrainEngine(
        make_supervised_loss(model, criterion),
        optax.sgd(0.05, momentum=0.9),
        mesh,
        sharding_rules=rules,
        fsdp_min_size=min_size,
    )
    state = engine.init_state(
        jax.random.key(seed), lambda r: model.init(r, jnp.zeros((1, 16, 16, 3)))
    )
    for i in range(steps):  # make step/opt-state momentum non-trivial
        rng = np.random.RandomState(i)
        batch = engine.shard_batch(
            {
                "image": rng.randn(8, 16, 16, 3).astype(np.float32),
                "label": rng.randint(0, 4, size=(8,)).astype(np.int32),
            }
        )
        state, _ = engine.train_step(state, batch)
    return engine, state


def _leaves_equal(a_state, b_state, *, opt=True):
    for a, b in zip(jax.tree.leaves(a_state.params), jax.tree.leaves(b_state.params), strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    if opt:
        for a, b in zip(jax.tree.leaves(a_state.opt_state), jax.tree.leaves(b_state.opt_state), strict=True):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


SHARDED_AXES = {mesh_lib.DATA_AXIS: 2, mesh_lib.FSDP_AXIS: 2, mesh_lib.TENSOR_AXIS: 2}


@pytest.mark.slow
def test_sharded_roundtrip_same_mesh(tmp_path, devices):
    """FSDP+TP-sharded state (momentum + step included) survives save/restore
    onto the same mesh, and the restored leaves land with the target's
    shardings (not replicated)."""
    from distributed_training_pytorch_tpu.parallel.sharding import transformer_tp_rules

    engine, state = _vit_engine(
        devices, SHARDED_AXES, rules=transformer_tp_rules(), min_size=1024, steps=2
    )
    specs = [
        str(l.sharding.spec) for l in jax.tree.leaves(state.params) if hasattr(l, "sharding")
    ]
    assert any("fsdp" in s for s in specs) and any("tensor" in s for s in specs), specs

    mgr = CheckpointManager(tmp_path / "c", async_save=False)
    mgr.save(LAST, state, epoch=3)
    mgr.close()

    engine2, target = _vit_engine(
        devices, SHARDED_AXES, rules=transformer_tp_rules(), min_size=1024, seed=1
    )
    mgr2 = CheckpointManager(tmp_path / "c", async_save=False)
    restored, epoch = mgr2.restore(LAST, target)
    mgr2.close()
    assert epoch == 3
    assert int(restored.step) == 2
    _leaves_equal(state, restored)
    # restored leaves keep the engine's sharded layout
    r_specs = [
        str(l.sharding.spec) for l in jax.tree.leaves(restored.params) if hasattr(l, "sharding")
    ]
    assert any("fsdp" in s for s in r_specs) and any("tensor" in s for s in r_specs), r_specs
    # and the engine can keep training from the restored state on its mesh
    rng = np.random.RandomState(9)
    batch = engine2.shard_batch(
        {
            "image": rng.randn(8, 16, 16, 3).astype(np.float32),
            "label": rng.randint(0, 4, size=(8,)).astype(np.int32),
        }
    )
    stepped, m = engine2.train_step(restored, batch)
    assert np.isfinite(float(m["loss"]))
    assert int(stepped.step) == 3


@pytest.mark.slow
def test_sharded_restore_onto_different_topology(tmp_path, devices):
    """A checkpoint saved from an 8-device data*fsdp*tensor mesh restores onto
    (a) a 4-device fsdp*tensor mesh and (b) a single-device replicated mesh —
    the resume-after-resize capability at pod scale."""
    from distributed_training_pytorch_tpu.parallel.sharding import transformer_tp_rules

    _, state = _vit_engine(
        devices, SHARDED_AXES, rules=transformer_tp_rules(), min_size=1024, steps=2
    )
    mgr = CheckpointManager(tmp_path / "c", async_save=False)
    mgr.save(LAST, state, epoch=5)
    mgr.close()

    # (a) fewer devices, different axis shape
    engine4, target4 = _vit_engine(
        devices[:4],
        {mesh_lib.FSDP_AXIS: 2, mesh_lib.TENSOR_AXIS: 2},
        rules=transformer_tp_rules(),
        min_size=1024,
        seed=2,
    )
    mgr = CheckpointManager(tmp_path / "c", async_save=False)
    restored4, epoch = mgr.restore(LAST, target4)
    assert epoch == 5
    _leaves_equal(state, restored4)
    batch_rng = np.random.RandomState(3)
    batch = engine4.shard_batch(
        {
            "image": batch_rng.randn(4, 16, 16, 3).astype(np.float32),
            "label": batch_rng.randint(0, 4, size=(4,)).astype(np.int32),
        }
    )
    _, m = engine4.train_step(restored4, batch)
    assert np.isfinite(float(m["loss"]))

    # (b) single device, fully replicated target
    _, target1 = _vit_engine(devices[:1], {mesh_lib.DATA_AXIS: 1}, seed=3)
    restored1, _ = mgr.restore(LAST, target1)
    mgr.close()
    _leaves_equal(state, restored1)


def test_meta_records_param_layout_and_reads_back(tmp_path, shared):
    """save() records the param tree's top level; read_meta returns it
    without a restore target — the wrapper-layout auto-select contract
    (examples/eval.py builds InputNormalizer targets from it)."""
    _, state, _ = shared
    mgr = CheckpointManager(tmp_path / "c", async_save=False)
    mgr.save(LAST, state, epoch=2)
    meta = mgr.read_meta(LAST)
    assert meta["epoch"] == 2
    assert meta["params_top_level"] == sorted(state.params.keys())

    # a wrapped-layout state (params nested under 'inner') records that
    wrapped = state.replace(params={"inner": state.params})
    mgr.save("wrapped", wrapped, epoch=3)
    assert mgr.read_meta("wrapped")["params_top_level"] == ["inner"]
    mgr.close()
