"""Mixed-precision subsystem (ISSUE 3): dtype policies, dynamic loss scaling,
and their integration through engine / trainer / checkpoint.

THE acceptance properties: the default ``precision="fp32"`` path is bit-exact
with pre-precision behavior; bf16 computes in bf16 while master weights and
optimizer state stay fp32; fp16 dynamic scaling grows/backs-off/skips fully
inside the compiled step; an overflow-skip and a nan-skip are ONE counted
event; chained bf16 windows are bit-exact with single-step bf16; and scale
state survives checkpoint/resume (including restoring a pre-precision
checkpoint with a fresh default scale).

Cost note: trainer-level tests use a tiny Dense net (seconds of CPU compile),
not the toy VGG of test_trainer.py — every case here constructs its own
trainer, so each must stay cheap.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from flax import linen as nn

from distributed_training_pytorch_tpu.checkpoint import CheckpointManager
from distributed_training_pytorch_tpu.data import ArrayDataSource
from distributed_training_pytorch_tpu.fault import FaultPlan
from distributed_training_pytorch_tpu.ops import cross_entropy_loss
from distributed_training_pytorch_tpu.parallel import mesh as mesh_lib
from distributed_training_pytorch_tpu.precision import (
    DynamicScale,
    NoOpScale,
    Policy,
    compute_dtype,
    get_policy,
    is_dynamic,
    model_dtype_for_entry,
    resolve_loss_scale,
)
from distributed_training_pytorch_tpu.train import TrainEngine, make_supervised_loss
from distributed_training_pytorch_tpu.trainer import Trainer

from test_engine import (
    CHAINED_VS_SINGLE_ULPS,
    TinyMLP,
    assert_trees_within_ulps,
    criterion,
    synthetic_batch,
)


@pytest.fixture(scope="module")
def mesh(devices):
    return mesh_lib.create_mesh({mesh_lib.DATA_AXIS: 8}, devices=devices)


def make_engine(seen=None, **engine_kw):
    """TinyMLP engine; ``seen`` (a dict) records the param dtype the loss fn
    actually receives — i.e. what dtype the model computes in."""
    mesh = mesh_lib.create_mesh()
    model = TinyMLP()
    base = make_supervised_loss(model, criterion)

    def loss_fn(params, model_state, batch, rng, train):
        if seen is not None:  # trace-time probe
            seen["param_dtype"] = str(jax.tree.leaves(params)[0].dtype)
            seen["input_dtype"] = str(batch["image"].dtype)
        return base(params, model_state, batch, rng, train)

    engine = TrainEngine(loss_fn, optax.sgd(0.05, momentum=0.9), mesh, **engine_kw)
    state = engine.init_state(
        jax.random.key(0), lambda rng: model.init(rng, jnp.zeros((1, 4, 4, 3)))
    )
    return engine, state


def stack_batches(host_batches):
    return jax.tree.map(lambda *xs: np.stack(xs), *host_batches)


def assert_trees_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb, strict=True):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# Policy resolution + casting rules.


def test_policy_presets_and_aliases():
    assert get_policy(None).name == "fp32" and not get_policy(None).active
    assert get_policy("bfloat16") is get_policy("bf16")
    assert get_policy("fp16").compute_dtype == jnp.float16
    for name in ("fp32", "bf16", "fp16"):
        assert get_policy(name).param_dtype == jnp.float32  # master weights
    assert compute_dtype("bf16") == jnp.bfloat16
    p = Policy(jnp.float32, jnp.bfloat16, jnp.float32, name="custom")
    assert get_policy(p) is p
    with pytest.raises(ValueError, match="unknown precision"):
        get_policy("int8")


def test_cast_inputs_leaves_integers_alone():
    policy = get_policy("bf16")
    batch = {
        "image": jnp.zeros((2, 4), jnp.float32),
        "label": jnp.zeros((2,), jnp.int32),
        "raw": jnp.zeros((2,), jnp.uint8),
    }
    cast = policy.cast_inputs(batch)
    assert cast["image"].dtype == jnp.bfloat16
    assert cast["label"].dtype == jnp.int32
    assert cast["raw"].dtype == jnp.uint8


def test_model_dtype_for_entry_follows_resolved_policy():
    """The one entry-knob resolution rule: an ACTIVE policy wins however it
    was set (explicit ctor override included), the inactive fp32 policy with
    an explicit env 'fp32' means float32, and an unset knob keeps the
    entry's legacy dtype."""
    # explicit precision= override, env unset: the policy wins (the bug this
    # helper replaced: a per-entry env read built a bf16 model under fp16)
    assert model_dtype_for_entry("fp16", True, jnp.bfloat16) == jnp.float16
    assert model_dtype_for_entry("bf16", True, jnp.bfloat16) == jnp.bfloat16
    # an EXPLICIT fp32 request (env knob or ctor arg) means float32 even
    # though the resolved policy is identical to the unset default
    assert model_dtype_for_entry("fp32", True, jnp.bfloat16) == jnp.float32
    # fully unset knob + default policy = the entry's historical program
    assert model_dtype_for_entry(None, False, jnp.bfloat16) == jnp.bfloat16
    assert model_dtype_for_entry(None, False) == jnp.float32  # digits-style


def test_resolve_loss_scale_auto():
    assert resolve_loss_scale(None, get_policy("bf16")) is None
    assert is_dynamic(resolve_loss_scale(None, get_policy("fp16")))
    assert isinstance(resolve_loss_scale("none", get_policy("bf16")), NoOpScale)
    assert is_dynamic(resolve_loss_scale("dynamic", get_policy("bf16")))
    with pytest.raises(ValueError, match="unknown loss_scale"):
        resolve_loss_scale("static", get_policy("fp16"))


# ---------------------------------------------------------------------------
# DynamicScale protocol (pure, no engine).


def test_dynamic_scale_grow_backoff_skip():
    s = DynamicScale.create(initial_scale=1024.0, growth_interval=2)
    ok = jnp.asarray(True)
    bad = jnp.asarray(False)
    s = s.adjust(ok)  # counter 1, no growth yet
    assert float(s.scale) == 1024.0 and int(s.growth_counter) == 1
    s = s.adjust(ok)  # counter hits interval -> x2, counter resets
    assert float(s.scale) == 2048.0 and int(s.growth_counter) == 0
    s = s.adjust(bad)  # overflow -> /2, skip counted, counter resets
    assert float(s.scale) == 1024.0
    assert int(s.skipped_steps) == 1
    assert int(s.growth_counter) == 0
    # clamps: backoff floors at min_scale, growth caps at max_scale
    tiny = DynamicScale.create(initial_scale=1.0, min_scale=1.0)
    assert float(tiny.adjust(bad).scale) == 1.0
    big = DynamicScale.create(initial_scale=2.0**24, growth_interval=1, max_scale=2.0**24)
    assert float(big.adjust(ok).scale) == 2.0**24


def test_dynamic_scale_unscale_is_exact():
    s = DynamicScale.create(initial_scale=2.0**15)
    grads = {"w": jnp.asarray([3.0, -7.25], jnp.float32)}
    scaled = jax.tree.map(lambda g: g * s.scale, grads)
    np.testing.assert_array_equal(
        np.asarray(s.unscale_grads(scaled)["w"]), np.asarray(grads["w"])
    )


# ---------------------------------------------------------------------------
# Engine: default fp32 bit-exactness, bf16 master weights, fp16 scaling.


def test_default_fp32_bit_exact_with_explicit_policy(devices):
    """The pre-PR acceptance proxy: the default engine (no precision args —
    the exact pre-precision construction) and an engine with the fp32 policy
    + NoOpScale spelled out produce bit-identical params/opt_state/metrics."""
    e1, s1 = make_engine()
    e2, s2 = make_engine(precision="fp32", loss_scale=NoOpScale())
    assert s1.loss_scale is None  # default state layout unchanged
    b = synthetic_batch(16, seed=0)
    for _ in range(3):
        s1, m1 = e1.train_step(s1, e1.shard_batch(b))
        s2, m2 = e2.train_step(s2, e2.shard_batch(b))
    assert_trees_equal(s1.params, s2.params)
    assert_trees_equal(s1.opt_state, s2.opt_state)
    for k in dict(m1):
        np.testing.assert_array_equal(np.asarray(m1[k]), np.asarray(m2[k]))
    assert "nonfinite" not in dict(m1)  # no guard materialized by default


def test_bf16_master_weights_fp32_round_trip(devices):
    """bf16 policy: the loss fn sees bf16 params/inputs (compute dtype) while
    the state's master weights stay fp32 and keep taking fp32 updates."""
    seen = {}
    engine, state = make_engine(seen=seen, precision="bf16")
    b = synthetic_batch(16, seed=1)
    losses = []
    for _ in range(20):
        state, metrics = engine.train_step(state, engine.shard_batch(b))
        losses.append(float(metrics["ce_loss"]))
    assert seen["param_dtype"] == "bfloat16"
    assert seen["input_dtype"] == "bfloat16"
    for leaf in jax.tree.leaves(state.params):
        assert str(leaf.dtype) == "float32"
    for leaf in jax.tree.leaves(state.opt_state):
        if jnp.issubdtype(leaf.dtype, jnp.floating):
            assert str(leaf.dtype) == "float32"
    assert losses[-1] < losses[0] * 0.5, losses
    # fp32 master accumulation: 20 bf16-rounded updates of lr*grad ~1e-3
    # magnitude must actually move the weights (a bf16 master would stall
    # once updates drop below ~1/256 of the weight scale).
    assert int(state.step) == 20


def test_fp16_dynamic_scale_grows_in_engine(devices):
    engine, state = make_engine(
        precision="fp16", loss_scale=DynamicScale.create(growth_interval=4)
    )
    b = synthetic_batch(16, seed=2)
    for _ in range(8):
        state, metrics = engine.train_step(state, engine.shard_batch(b))
    # two full growth intervals of finite steps: 2^15 -> 2^17
    assert float(state.loss_scale.scale) == 2.0**17
    assert int(state.loss_scale.skipped_steps) == 0
    m = dict(metrics)
    assert float(m["nonfinite"]) == 0.0
    # the metric reports the scale the step USED (pre-adjust): step 8 ran at
    # 2^16 and grew to 2^17 on completion
    assert float(m["loss_scale"]) == 2.0**16
    # the reported loss is the UNSCALED fp32 loss
    assert float(m["ce_loss"]) < 10.0


def test_fp16_overflow_skips_step_and_backs_off(devices):
    engine, state = make_engine(precision="fp16", loss_scale=DynamicScale.create())
    b = synthetic_batch(16, seed=3)
    state, _ = engine.train_step(state, engine.shard_batch(b))
    params_before = jax.tree.map(lambda x: np.array(x), state.params)
    poisoned = dict(b, image=np.full_like(b["image"], np.nan))
    state, metrics = engine.train_step(state, engine.shard_batch(poisoned))
    assert float(metrics["nonfinite"]) == 1.0
    assert_trees_equal(params_before, state.params)  # update dropped
    assert float(state.loss_scale.scale) == 2.0**14  # backed off
    assert int(state.loss_scale.skipped_steps) == 1
    assert int(state.step) == 2  # step still advances past the poison


def test_bf16_chained_bit_exact_with_single_step(devices):
    """A bf16 chained window == the same steps run singly: opt_state and every
    per-step metric equal (on every seed measured), the float32 master params
    within CHAINED_VS_SINGLE_ULPS."""
    host = [synthetic_batch(16, seed=60 + i) for i in range(4)]
    eng_a, state_a = make_engine(precision="bf16")
    eng_b, state_b = make_engine(precision="bf16")
    seq_metrics = []
    for hb in host:
        state_a, m = eng_a.train_step(state_a, eng_a.shard_batch(hb))
        seq_metrics.append(jax.device_get(m))
    gb = mesh_lib.global_chain_array_from_host_local(stack_batches(host), eng_b.mesh)
    state_b, stacked = eng_b.train_steps_chained(state_b, gb, 4)
    assert int(state_b.step) == int(state_a.step) == 4
    assert_trees_within_ulps(state_a.params, state_b.params, CHAINED_VS_SINGLE_ULPS)
    assert_trees_equal(state_a.opt_state, state_b.opt_state)
    stacked = jax.device_get(stacked)
    for i, m in enumerate(seq_metrics):
        for k, v in m.items():
            np.testing.assert_array_equal(np.asarray(v), np.asarray(stacked[k][i]))


def test_fp16_chained_carries_scale_state(devices):
    """Dynamic-scale state rides the chained scan: growth inside a window
    matches the sequential run exactly."""
    host = [synthetic_batch(16, seed=70 + i) for i in range(4)]
    kw = dict(precision="fp16", loss_scale=DynamicScale.create(growth_interval=2))
    eng_a, state_a = make_engine(**kw)
    eng_b, state_b = make_engine(**kw)
    for hb in host:
        state_a, _ = eng_a.train_step(state_a, eng_a.shard_batch(hb))
    gb = mesh_lib.global_chain_array_from_host_local(stack_batches(host), eng_b.mesh)
    state_b, stacked = eng_b.train_steps_chained(state_b, gb, 4)
    assert float(state_b.loss_scale.scale) == float(state_a.loss_scale.scale) == 2.0**17
    assert_trees_equal(state_a.params, state_b.params)
    # per-step loss_scale metrics stack as scan outputs
    np.testing.assert_array_equal(
        np.asarray(jax.device_get(stacked)["loss_scale"]),
        np.array([2.0**15, 2.0**15, 2.0**16, 2.0**16]),
    )


def test_fp16_microbatch_accumulation_unscales_once(devices):
    """The accum scan accumulates SCALED grads and unscales after: fp16
    accum-2 must track fp16 accum-1 closely on the same data (same policy,
    same scale — values differ only by half-precision summation order)."""
    b = synthetic_batch(32, seed=4)
    e1, s1 = make_engine(precision="fp16", loss_scale=DynamicScale.create())
    e2, s2 = make_engine(
        precision="fp16", loss_scale=DynamicScale.create(), accum_steps=2
    )
    s1, m1 = e1.train_step(s1, e1.shard_batch(b))
    s2, m2 = e2.train_step(s2, e2.shard_batch(b))
    assert float(m1["nonfinite"]) == float(m2["nonfinite"]) == 0.0
    for a, c in zip(jax.tree.leaves(s1.params), jax.tree.leaves(s2.params), strict=True):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c), atol=2e-3)


# ---------------------------------------------------------------------------
# Checkpoint: scale state round-trip + pre-precision compatibility.


def test_checkpoint_scale_state_round_trip(devices, tmp_path):
    engine, state = make_engine(precision="fp16", loss_scale=DynamicScale.create())
    state = state.replace(
        loss_scale=state.loss_scale.replace(
            scale=jnp.asarray(1024.0, jnp.float32),
            growth_counter=jnp.asarray(5, jnp.int32),
            skipped_steps=jnp.asarray(7, jnp.int32),
        )
    )
    mgr = CheckpointManager(tmp_path / "ckpt", async_save=False)
    mgr.save("last", state, 3)
    assert mgr.read_meta("last")["loss_scale"] == "DynamicScale"
    _, target = make_engine(precision="fp16", loss_scale=DynamicScale.create())
    restored, epoch = mgr.restore("last", target)
    assert epoch == 3
    assert float(restored.loss_scale.scale) == 1024.0
    assert int(restored.loss_scale.growth_counter) == 5
    assert int(restored.loss_scale.skipped_steps) == 7


def test_checkpoint_pre_precision_loads_with_fresh_scale(devices, tmp_path):
    """A checkpoint saved WITHOUT scale state (the pre-precision layout —
    default engines still write exactly it) restores into a dynamic-scale
    target with the target's fresh default scale."""
    mgr = CheckpointManager(tmp_path / "ckpt", async_save=False)
    _, old_state = make_engine()  # loss_scale=None -> no scale item on disk
    mgr.save("last", old_state, 1)
    assert not os.path.isdir(os.path.join(str(tmp_path / "ckpt"), "last", "scale"))
    assert "loss_scale" not in mgr.read_meta("last")
    _, target = make_engine(
        precision="fp16", loss_scale=DynamicScale.create(initial_scale=2.0**15)
    )
    restored, _ = mgr.restore("last", target)
    assert float(restored.loss_scale.scale) == 2.0**15  # fresh default
    assert int(restored.loss_scale.skipped_steps) == 0
    # and the reverse: a scale-carrying checkpoint under an fp32 target
    eng_fp16, st_fp16 = make_engine(precision="fp16", loss_scale=DynamicScale.create())
    mgr.save("fp16", st_fp16, 2)
    _, plain_target = make_engine()
    restored2, _ = mgr.restore("fp16", plain_target)
    assert restored2.loss_scale is None


# ---------------------------------------------------------------------------
# Trainer integration: ctor knob + validation, single-count accounting,
# TensorBoard emission.


class MiniNet(nn.Module):
    @nn.compact
    def __call__(self, x, *, train: bool = False):
        x = x.reshape(x.shape[0], -1)
        x = nn.Dense(16)(x)
        x = nn.relu(x)
        return nn.Dense(3)(x)


class MiniTrainer(Trainer):
    """Cheap trainer (Dense net, 4x4 images) — each precision case builds its
    own, so construction must cost seconds, not the toy VGG's ~15-40s."""

    def build_train_dataset(self):
        rng = np.random.RandomState(0)
        labels = rng.randint(0, 3, size=(64,)).astype(np.int32)
        images = (rng.randn(64, 4, 4, 3) + labels[:, None, None, None]).astype(
            np.float32
        )
        return ArrayDataSource(image=images, label=labels)

    def build_model(self):
        return MiniNet()

    def build_criterion(self):
        def crit(logits, batch):
            loss = cross_entropy_loss(logits, batch["label"])
            return loss, {"loss": loss}

        return crit

    def build_optimizer(self, schedule):
        return optax.sgd(schedule)

    def build_scheduler(self):
        return 0.05


def make_mini(tmp_path, mesh, **kw):
    defaults = dict(
        max_epoch=2,
        batch_size=16,
        save_folder=str(tmp_path / "runs"),
        num_workers=0,
        log_every=0,
        async_checkpoint=False,
        mesh=mesh,
        progress=False,
        logger=type("Q", (), {"log": staticmethod(lambda *a, **k: None)})(),
    )
    defaults.update(kw)
    return MiniTrainer(**defaults)


def test_trainer_rejects_fp16_without_scaling(tmp_path, mesh):
    with pytest.raises(ValueError, match="requires dynamic loss scaling"):
        make_mini(tmp_path, mesh, precision="fp16", loss_scale="none")


def test_trainer_rejects_dynamic_scale_with_nan_raise(tmp_path, mesh):
    with pytest.raises(ValueError, match="incompatible with dynamic loss"):
        make_mini(tmp_path, mesh, precision="fp16", nan_policy="raise")
    # restore_last_good would roll the whole state back (and undo the
    # backoff) on every benign calibration overflow — also rejected
    with pytest.raises(ValueError, match="incompatible with dynamic loss"):
        make_mini(tmp_path, mesh, precision="fp16", nan_policy="restore_last_good")


def test_trainer_fp16_defaults_to_dynamic_scale(tmp_path, mesh):
    t = make_mini(tmp_path, mesh, precision="fp16")
    assert is_dynamic(t.state.loss_scale)
    assert t.model_dtype == jnp.float16
    t.train()
    assert int(t.state.loss_scale.skipped_steps) == 0
    assert int(t.state.step) == 8


def test_trainer_overflow_and_nan_counted_once(tmp_path, mesh):
    """The reconciliation clause: with BOTH nan_policy='skip' (engine guard)
    and a DynamicScale active, a poisoned step is one event — one engine
    skip, one nonfinite_steps count, one loss-scale skip — never two."""
    plan = FaultPlan().add("nan_loss", epoch=0, step=1)
    t = make_mini(
        tmp_path,
        mesh,
        precision="fp16",
        nan_policy="skip",
        fault_plan=plan,
    )
    t.train()
    assert t.fault_plan.count_fired("nan_loss") == 1
    assert t.nonfinite_steps == 1  # counted once, not twice
    assert int(t.state.loss_scale.skipped_steps) == 1
    assert float(t.state.loss_scale.scale) == 2.0**14  # one backoff
    for leaf in jax.tree.leaves(t.state.params):
        assert np.isfinite(np.asarray(leaf)).all()


def test_trainer_default_precision_is_fp32_and_scale_free(tmp_path, mesh):
    t = make_mini(tmp_path, mesh)
    assert t.precision.name == "fp32" and not t.precision.active
    assert t.state.loss_scale is None
    assert t.model_dtype == jnp.float32
    assert t.precision_requested is False
    # an explicit "fp32" resolves to the same policy but records the request
    t2 = make_mini(tmp_path, mesh, precision="fp32")
    assert t2.precision.name == "fp32" and t2.precision_requested is True


def test_metrics_writer_noop_without_tensorboardx(tmp_path, mesh, monkeypatch):
    """tensorboard_dir set but tensorboardX unimportable: the writer stays a
    no-op and the precision scalars path (loss_scale/skipped_steps emission)
    runs silently through a full dynamic-scale training."""
    monkeypatch.setitem(sys.modules, "tensorboardX", None)  # import -> ImportError
    t = make_mini(
        tmp_path,
        mesh,
        precision="fp16",
        tensorboard_dir=str(tmp_path / "tb"),
    )
    assert not t.metrics_writer.active
    t.train()  # _write_precision_scalars must be a silent no-op throughout
    assert not t.metrics_writer.active
    assert not os.path.exists(str(tmp_path / "tb"))  # nothing was written


def test_trainer_bf16_resume_preserves_behavior(tmp_path, mesh):
    """bf16 trainer saves/resumes through the normal checkpoint path (scale
    layout = pre-precision: NoOpScale-free state, no scale item)."""
    t = make_mini(tmp_path, mesh, precision="bf16", max_epoch=1, save_period=1)
    t.train()
    ckpt = os.path.join(t.save_weight_folder, "checkpoint_epoch_1")
    t2 = make_mini(
        tmp_path, mesh, precision="bf16", max_epoch=2,
        save_period=1, snapshot_path=ckpt if os.path.isdir(ckpt) else "latest_valid",
    )
    assert int(t2.state.step) == 4  # resumed mid-schedule
    t2.train()
    assert int(t2.state.step) == 8
