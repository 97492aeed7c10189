"""Fault-tolerance subsystem tests (fault/ + crash-consistent checkpointing +
verified resume + graceful degradation).

The two acceptance pillars:

* kill training mid-epoch with an injected (real) SIGTERM, resume from the
  auto-saved snapshot, and land BIT-EXACT on the uninterrupted run's params;
* corrupt the newest checkpoint on disk and watch restore fall back to the
  newest *valid* one instead of crashing — with saves atomic throughout
  (a failed save never damages the previously-committed checkpoint).
"""

import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_training_pytorch_tpu.checkpoint import (
    LAST,
    CheckpointError,
    CheckpointManager,
    CorruptCheckpointError,
)
from distributed_training_pytorch_tpu.data import ArrayDataSource, ShardedLoader
from distributed_training_pytorch_tpu.data.records import (
    CorruptRecordError,
    RecordFileSource,
)
from distributed_training_pytorch_tpu.fault import (
    CorruptingSource,
    FaultPlan,
    StepWatchdog,
    corrupt_checkpoint,
)
from distributed_training_pytorch_tpu.parallel import mesh as mesh_lib
from distributed_training_pytorch_tpu.train import NonFiniteLossError, TrainState

from test_engine import CHAINED_VS_SINGLE_ULPS, assert_trees_within_ulps
from test_trainer import make_trainer, synthetic_images


@pytest.fixture(scope="module")
def mesh(devices):
    return mesh_lib.create_mesh({mesh_lib.DATA_AXIS: 8}, devices=devices)


# ---------------------------------------------------------------------------
# CheckpointManager: atomic commits, integrity, retry, newest-valid fallback.
# A bare TrainState avoids the ~20s model-compile cost of the trainer tests.


def _tiny_state(seed=0, step=0):
    rng = np.random.RandomState(seed)
    return TrainState(
        step=jnp.asarray(step, jnp.int32),
        params={"w": jnp.asarray(rng.randn(4, 3), jnp.float32)},
        opt_state={"m": jnp.zeros((4, 3), jnp.float32)},
        model_state={},
        rng=jax.random.key(seed),
    )


def test_manifest_validate_and_corruption_modes(tmp_path):
    mgr = CheckpointManager(tmp_path / "c", async_save=False)
    mgr.save(LAST, _tiny_state(), epoch=1)
    mgr.validate(LAST)  # fresh commit passes

    corrupt_checkpoint(mgr.path(LAST), mode="flip")
    with pytest.raises(CorruptCheckpointError, match="hash mismatch"):
        mgr.validate(LAST)
    with pytest.raises(CorruptCheckpointError):
        mgr.restore(LAST, _tiny_state(seed=9))

    mgr.save(LAST, _tiny_state(), epoch=1)  # overwrite repairs
    corrupt_checkpoint(mgr.path(LAST), mode="truncate")
    with pytest.raises(CorruptCheckpointError, match="torn write"):
        mgr.validate(LAST)

    mgr.save(LAST, _tiny_state(), epoch=1)
    corrupt_checkpoint(mgr.path(LAST), mode="delete")
    with pytest.raises(CorruptCheckpointError, match="missing file"):
        mgr.validate(LAST)
    mgr.close()


def test_corrupt_latest_falls_back_to_newest_valid(tmp_path):
    """The acceptance scenario: latest checkpoint corrupt -> restore falls
    back to the previous valid one instead of crashing."""
    state1, state2 = _tiny_state(seed=1, step=10), _tiny_state(seed=2, step=20)
    mgr = CheckpointManager(tmp_path / "c", async_save=False)
    mgr.save("checkpoint_epoch_1", state1, epoch=1)
    time.sleep(0.05)  # distinct mtimes for newest-first ordering
    mgr.save(LAST, state2, epoch=2)

    corrupt_checkpoint(mgr.path(LAST), mode="truncate")
    restored, epoch, name = mgr.restore_latest_valid(_tiny_state(seed=9))
    assert name == "checkpoint_epoch_1" and epoch == 1
    np.testing.assert_array_equal(
        np.asarray(restored.params["w"]), np.asarray(state1.params["w"])
    )
    mgr.close()

    # nothing valid at all -> a catchable CheckpointError, not a crash
    mgr2 = CheckpointManager(tmp_path / "c2", async_save=False)
    mgr2.save(LAST, state1, epoch=1)
    corrupt_checkpoint(mgr2.path(LAST), mode="flip")
    with pytest.raises(CheckpointError):
        mgr2.restore_latest_valid(_tiny_state(seed=9))
    mgr2.close()


def test_transient_write_failure_retries_then_succeeds(tmp_path):
    plan = FaultPlan().add("checkpoint_write", count=2)
    mgr = CheckpointManager(
        tmp_path / "c",
        async_save=False,
        save_retries=2,
        retry_backoff=0.01,
        fault_plan=plan,
    )
    mgr.save(LAST, _tiny_state(step=5), epoch=3)  # attempts 1+2 fail, 3 lands
    assert plan.count_fired("checkpoint_write") == 2
    mgr.validate(LAST)
    _, epoch = mgr.restore(LAST, _tiny_state(seed=9))
    assert epoch == 3
    mgr.close()


def test_failed_save_is_atomic_old_checkpoint_survives(tmp_path):
    """A save that exhausts its retries must leave the previously committed
    checkpoint fully intact under the final name (atomicity guarantee)."""
    state_good = _tiny_state(seed=1, step=1)
    plan = FaultPlan()
    mgr = CheckpointManager(
        tmp_path / "c",
        async_save=False,
        save_retries=1,
        retry_backoff=0.01,
        fault_plan=plan,
    )
    mgr.save(LAST, state_good, epoch=1)  # clean commit
    plan.add("checkpoint_write", count=10)  # now every attempt fails
    with pytest.raises(CheckpointError, match="failed after 2 attempts"):
        mgr.save(LAST, _tiny_state(seed=2, step=2), epoch=2)
    mgr.validate(LAST)  # old checkpoint still valid under the final name
    restored, epoch = mgr.restore(LAST, _tiny_state(seed=9))
    assert epoch == 1
    np.testing.assert_array_equal(
        np.asarray(restored.params["w"]), np.asarray(state_good.params["w"])
    )
    mgr.close()


def test_first_save_write_failure_raises_and_leaves_nothing(tmp_path):
    plan = FaultPlan().add("checkpoint_write", count=10)
    mgr = CheckpointManager(
        tmp_path / "c",
        async_save=False,
        save_retries=1,
        retry_backoff=0.01,
        fault_plan=plan,
    )
    with pytest.raises(CheckpointError):
        mgr.save(LAST, _tiny_state(), epoch=1)
    assert not mgr.exists(LAST)  # no partial checkpoint under the final name
    mgr.close()


def test_crash_mid_swap_recovers_on_next_manager(tmp_path):
    """Crash between the two commit renames leaves only `<name>.old`; the
    next manager construction rolls it back."""
    mgr = CheckpointManager(tmp_path / "c", async_save=False)
    mgr.save(LAST, _tiny_state(step=7), epoch=4)
    mgr.close()
    final = os.path.join(str(tmp_path / "c"), LAST)
    os.rename(final, final + ".old")  # simulate the crash window

    mgr2 = CheckpointManager(tmp_path / "c", async_save=False)
    assert mgr2.exists(LAST)
    mgr2.validate(LAST)
    _, epoch = mgr2.restore(LAST, _tiny_state(seed=9))
    assert epoch == 4
    mgr2.close()


def test_loop_state_round_trips_through_meta(tmp_path):
    mgr = CheckpointManager(tmp_path / "c", async_save=False)
    mgr.save(LAST, _tiny_state(), epoch=2, loop_state={"step_in_epoch": 3})
    assert mgr.read_meta(LAST)["loop"] == {"step_in_epoch": 3}
    mgr.close()


# ---------------------------------------------------------------------------
# Data-path degradation: corrupt records skip-and-count.


def _write_shard(tmp_path, n=12):
    import cv2

    from distributed_training_pytorch_tpu.data.records import write_shards

    def records():
        rng = np.random.RandomState(0)
        for i in range(n):
            img = rng.randint(0, 255, size=(8, 8, 3), dtype=np.uint8)
            ok, buf = cv2.imencode(".png", img)
            assert ok
            yield buf.tobytes(), i % 3

    return write_shards(str(tmp_path / "train"), records(), num_shards=1)[0]


def _corrupt_record_length(path, source, index):
    """Overwrite record `index`'s length field with garbage (structural
    corruption: payload would overrun the shard's payload region)."""
    offset = int(source._shard_offsets[0][index])
    with open(path, "rb+") as f:
        f.seek(offset + 8)  # label i64 then length u64
        f.write((2**40).to_bytes(8, "little"))


def test_corrupt_record_raises_typed_error(tmp_path):
    pytest.importorskip("cv2")
    shard = _write_shard(tmp_path)
    src = RecordFileSource(shard)
    _corrupt_record_length(shard, src, 5)
    with pytest.raises(CorruptRecordError, match="overruns"):
        src[5]
    assert src[4]["image"].shape == (8, 8, 3)  # neighbors unaffected


def test_loader_skips_and_counts_corrupt_records(tmp_path):
    pytest.importorskip("cv2")
    shard = _write_shard(tmp_path)
    src = RecordFileSource(shard)
    _corrupt_record_length(shard, src, 5)
    loader = ShardedLoader(
        src,
        4,
        shuffle=False,
        num_workers=0,
        skip_corrupt=True,
        process_index=0,
        process_count=1,
    )
    batches = list(loader)
    assert len(batches) == 3  # every batch produced despite the bad record
    assert src.corrupt_skipped == 1
    # substitution is deterministic: a second epoch pass skips the same way
    batches2 = list(loader)
    np.testing.assert_array_equal(batches[1]["image"], batches2[1]["image"])

    strict = ShardedLoader(
        RecordFileSource(shard), 4, shuffle=False, num_workers=0,
        process_index=0, process_count=1,
    )
    with pytest.raises(CorruptRecordError):
        list(strict)


def test_fast_path_batch_decode_tolerance(tmp_path):
    """Whole-batch (native fast path) decode failures degrade like the
    per-record path: the bad position's (payload, label) pair is substituted
    by the next readable record and counted."""
    pytest.importorskip("cv2")
    from distributed_training_pytorch_tpu.data.native import DecodeError

    shard = _write_shard(tmp_path)
    src = RecordFileSource(shard, skip_corrupt=True)
    rows = np.arange(4)
    payloads, labels = map(list, zip(*(src.read_record(int(i)) for i in rows), strict=True))
    bad_payload = payloads[2]

    def produce(pls):
        if pls[2] == bad_payload:  # "bit-rot": this payload never decodes
            raise DecodeError(2)
        return np.zeros((4, 8, 8, 3), np.uint8)

    out = src._produce_batch_tolerant(rows, payloads, labels, produce)
    assert out.shape == (4, 8, 8, 3)
    assert src.corrupt_skipped == 1
    assert (payloads[2], labels[2]) == src.read_record(3)  # neighbor pair

    strict = RecordFileSource(shard)
    p2, l2 = map(list, zip(*(strict.read_record(int(i)) for i in rows), strict=True))
    with pytest.raises(CorruptRecordError):
        strict._produce_batch_tolerant(rows, p2, l2, produce)


def test_completed_async_staging_promoted_on_recovery(tmp_path):
    """A finished-but-uncommitted write (process died between the async
    write's completion and the next wait()) is promoted on the next manager
    construction, not discarded."""
    mgr = CheckpointManager(tmp_path / "c", async_save=False)
    mgr.save(LAST, _tiny_state(step=3), epoch=2)
    mgr.close()
    final = os.path.join(str(tmp_path / "c"), LAST)
    staging_root = os.path.join(str(tmp_path / "c"), ".staging")
    os.makedirs(staging_root)
    os.rename(final, os.path.join(staging_root, "last.9"))
    os.remove(os.path.join(staging_root, "last.9", "manifest.dtp.json"))

    mgr2 = CheckpointManager(tmp_path / "c", async_save=False)
    assert mgr2.exists(LAST)
    mgr2.validate(LAST)
    _, epoch = mgr2.restore(LAST, _tiny_state(seed=9))
    assert epoch == 2
    mgr2.close()


def test_latest_valid_cold_start(tmp_path, mesh):
    """snapshot_path='latest_valid' on a first launch (nothing saved yet)
    must start fresh, not raise — the restart wrapper is idempotent."""
    trainer = make_trainer(
        tmp_path, mesh, max_epoch=1, have_validate=False, save_best_for=None,
        save_period=None, snapshot_path="latest_valid",
    )
    assert trainer.cur_epoch == 0


def test_injected_corrupt_record_via_fault_plan():
    images, labels = synthetic_images(16, seed=0)
    plan = FaultPlan().add("corrupt_record", step=5)
    src = CorruptingSource(ArrayDataSource(image=images, label=labels), plan)
    loader = ShardedLoader(
        src, 4, shuffle=False, num_workers=0, skip_corrupt=True,
        process_index=0, process_count=1,
    )
    assert len(list(loader)) == 4
    assert loader.corrupt_skipped == 1
    assert plan.count_fired("corrupt_record") == 1


# ---------------------------------------------------------------------------
# Watchdog.


def test_watchdog_fires_on_stall_and_not_on_progress():
    fired = []
    with StepWatchdog(0.08, lambda: fired.append(1), poll_interval=0.02) as dog:
        for _ in range(5):  # regular pats: no fire
            time.sleep(0.03)
            dog.pat()
        assert not fired
        time.sleep(0.2)  # stall: fires exactly once (max_fires=1)
    assert fired == [1]
    assert dog.fired == 1


def test_watchdog_rejects_bad_timeout():
    with pytest.raises(ValueError):
        StepWatchdog(0)


# ---------------------------------------------------------------------------
# Trainer integration: kill/resume bit-exactness, NaN policies, hung step.


def test_sigterm_mid_epoch_resume_is_bit_exact(tmp_path, mesh):
    """THE acceptance test: epoch 1 is killed at step 2 by an injected (real)
    SIGTERM; the run resumes from the auto-saved snapshot and finishes with
    params BIT-EXACT to an uninterrupted run's."""
    kw = dict(
        max_epoch=2, have_validate=False, save_best_for=None, save_period=None
    )
    baseline = make_trainer(tmp_path / "a", mesh, **kw)
    baseline.train()

    plan = FaultPlan().add("sigterm", epoch=1, step=2)
    interrupted = make_trainer(tmp_path / "b", mesh, fault_plan=plan, **kw)
    interrupted.train()
    assert interrupted._preempted and interrupted._epoch_interrupted
    assert interrupted.checkpoints.exists(LAST)
    meta = interrupted.checkpoints.read_meta(LAST)
    assert meta["epoch"] == 1 and meta["loop"] == {"step_in_epoch": 2}

    resumed = make_trainer(
        tmp_path / "b",
        mesh,
        snapshot_path=interrupted.checkpoints.path(LAST),
        **kw,
    )
    assert resumed.cur_epoch == 1 and resumed._resume_step_in_epoch == 2
    resumed.train()

    assert int(resumed.state.step) == int(baseline.state.step)
    for a, b in zip(
        jax.tree.leaves(baseline.state.params),
        jax.tree.leaves(resumed.state.params),
        strict=True,
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(
        jax.tree.leaves(baseline.state.opt_state),
        jax.tree.leaves(resumed.state.opt_state),
        strict=True,
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_sigterm_resume_crosses_window_boundary_chained(tmp_path, mesh):
    """Chained-mode preemption acceptance (ISSUE 2): a chain_steps=4 run is
    killed by an injected (real) SIGTERM at epoch 1, step 2 — inside the
    fault-active window [0,4), which therefore runs single-step, preserving
    exact per-step interruption semantics. The resume then REALIGNS: steps
    2-3 run single-step until the next window boundary, and [4,8) chains —
    finishing on the step count of an uninterrupted chain_steps=1 run, with
    params and opt_state within CHAINED_VS_SINGLE_ULPS of its."""
    kw = dict(
        max_epoch=2, batch_size=8, have_validate=False, save_best_for=None,
        save_period=None,
    )
    baseline = make_trainer(tmp_path / "a", mesh, **kw)
    baseline.train()

    plan = FaultPlan().add("sigterm", epoch=1, step=2)
    interrupted = make_trainer(
        tmp_path / "b", mesh, chain_steps=4, fault_plan=plan, **kw
    )
    interrupted.train()
    assert interrupted._preempted and interrupted._epoch_interrupted
    assert interrupted.checkpoints.exists(LAST)
    meta = interrupted.checkpoints.read_meta(LAST)
    assert meta["epoch"] == 1 and meta["loop"] == {"step_in_epoch": 2}
    # epoch 0 had no pending injections: it really chained (2 windows of 4)
    assert interrupted.engine.trace_counts["chained_4"] == 1

    resumed = make_trainer(
        tmp_path / "b",
        mesh,
        chain_steps=4,
        snapshot_path=interrupted.checkpoints.path(LAST),
        **kw,
    )
    assert resumed.cur_epoch == 1 and resumed._resume_step_in_epoch == 2
    resumed.train()

    assert int(resumed.state.step) == int(baseline.state.step)
    assert_trees_within_ulps(
        baseline.state.params, resumed.state.params, CHAINED_VS_SINGLE_ULPS
    )
    assert_trees_within_ulps(
        baseline.state.opt_state, resumed.state.opt_state, CHAINED_VS_SINGLE_ULPS
    )
    # realignment shape: 2 lead singles (steps 2-3), then ONE chained window
    assert resumed.engine.trace_counts["train_step"] == 1
    assert resumed.engine.trace_counts["chained_4"] == 1


def test_nan_policy_raise(tmp_path, mesh):
    plan = FaultPlan().add("nan_loss", epoch=0, step=1)
    trainer = make_trainer(
        tmp_path, mesh, max_epoch=1, have_validate=False, save_best_for=None,
        save_period=None, nan_policy="raise", fault_plan=plan,
    )
    with pytest.raises(NonFiniteLossError):
        trainer.train()


def test_nan_policy_skip_preserves_params_and_counts(tmp_path, mesh):
    plan = FaultPlan().add("nan_loss", epoch=0, step=1)
    trainer = make_trainer(
        tmp_path, mesh, max_epoch=1, have_validate=False, save_best_for=None,
        save_period=None, nan_policy="skip", fault_plan=plan,
    )
    trainer.train()
    assert trainer.nonfinite_steps == 1
    assert plan.count_fired("nan_loss") == 1
    # the poisoned step was dropped, not absorbed: params stayed finite
    for leaf in jax.tree.leaves(trainer.state.params):
        assert np.isfinite(np.asarray(leaf)).all()


def test_hung_step_watchdog_forces_resumable_save(tmp_path, mesh):
    """A simulated hung step (fault 'hang') trips the step watchdog, which
    SIGTERMs the process; the preemption machinery turns that into a
    resumable mid-epoch save."""
    plan = FaultPlan().add("hang", epoch=0, step=1, payload=0.8)
    trainer = make_trainer(
        tmp_path, mesh, max_epoch=1, have_validate=False, save_best_for=None,
        save_period=None, step_timeout=0.2, fault_plan=plan,
    )
    trainer.train()
    assert trainer._preempted
    assert trainer.checkpoints.exists(LAST)
    meta = trainer.checkpoints.read_meta(LAST)
    assert meta["loop"]["step_in_epoch"] == 1  # step 0 done, step 1 hung
