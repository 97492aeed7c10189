"""On-device chained step execution (ISSUE 2): engine scan windows, chain-major
prefetch staging, and the Trainer's windowed hot loop.

THE acceptance property throughout: chained execution runs the same
arithmetic as single-step execution on the same data/RNG — same step counts,
same integer state, float params / opt_state / per-step metrics within
``test_engine.CHAINED_VS_SINGLE_ULPS`` (and bit-equal wherever the two
programs happen to order their reductions alike) — across microbatching and
the nan guard, with automatic single-step fallback for epoch tails and
fault-injected windows.

Cost note: trainer constructions compile a toy VGG on CPU (~15-40s each), so
trainer-level tests share module-scoped runs the way test_trainer.py does.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_training_pytorch_tpu.data import ShardedLoader, ArrayDataSource
from distributed_training_pytorch_tpu.data.prefetch import device_prefetch_chained
from distributed_training_pytorch_tpu.fault import FaultPlan
from distributed_training_pytorch_tpu.parallel import mesh as mesh_lib
from distributed_training_pytorch_tpu.train import TrainEngine, make_supervised_loss

from test_engine import (
    CHAINED_VS_SINGLE_ULPS,
    TinyMLP,
    assert_trees_within_ulps,
    criterion,
    synthetic_batch,
)
from test_trainer import RecordingToyTrainer, ToyTrainer, make_trainer, synthetic_images


@pytest.fixture(scope="module")
def mesh(devices):
    return mesh_lib.create_mesh({mesh_lib.DATA_AXIS: 8}, devices=devices)


def make_engine(accum_steps=1, nan_guard=False):
    mesh = mesh_lib.create_mesh()
    model = TinyMLP()
    import optax

    engine = TrainEngine(
        make_supervised_loss(model, criterion),
        optax.sgd(0.05, momentum=0.9),
        mesh,
        accum_steps=accum_steps,
        nan_guard=nan_guard,
    )
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
# Engine: train_steps_chained.


def test_train_steps_chained_bit_exact_distinct_batches(devices):
    """4 distinct per-step batches through ONE chained dispatch == 4 sequential
    train_steps: the same step count, and params, opt_state and every per-step
    metric within CHAINED_VS_SINGLE_ULPS."""
    host = [synthetic_batch(16, seed=i) for i in range(4)]
    eng_a, state_a = make_engine()
    eng_b, state_b = make_engine()
    seq_metrics = []
    for hb in host:
        state_a, m = eng_a.train_step(state_a, eng_a.shard_batch(hb))
        seq_metrics.append(jax.device_get(m))
    gb = mesh_lib.global_chain_array_from_host_local(stack_batches(host), eng_b.mesh)
    state_b, stacked = eng_b.train_steps_chained(state_b, gb, 4)
    assert int(state_b.step) == int(state_a.step) == 4
    assert_trees_within_ulps(state_a.params, state_b.params, CHAINED_VS_SINGLE_ULPS)
    assert_trees_within_ulps(state_a.opt_state, state_b.opt_state, CHAINED_VS_SINGLE_ULPS)
    stacked = jax.device_get(stacked)
    for i, m in enumerate(seq_metrics):
        assert set(m) == set(stacked)
        assert_trees_within_ulps(
            m, {k: v[i] for k, v in stacked.items()}, CHAINED_VS_SINGLE_ULPS
        )


def test_train_steps_chained_microbatched_nan_guard_bit_exact(devices):
    """The chained scan threads the microbatch-accumulation scan AND the
    non-finite guard unchanged (they live inside the step body)."""
    host = [synthetic_batch(16, seed=10 + i) for i in range(3)]
    eng_a, state_a = make_engine(accum_steps=2, nan_guard=True)
    eng_b, state_b = make_engine(accum_steps=2, nan_guard=True)
    for hb in host:
        state_a, m = eng_a.train_step(state_a, eng_a.shard_batch(hb))
        assert float(m["nonfinite"]) == 0.0
    gb = mesh_lib.global_chain_array_from_host_local(stack_batches(host), eng_b.mesh)
    state_b, stacked = eng_b.train_steps_chained(state_b, gb, 3)
    assert_trees_equal(state_a.params, state_b.params)
    assert_trees_equal(state_a.opt_state, state_b.opt_state)
    np.testing.assert_array_equal(np.asarray(stacked["nonfinite"]), np.zeros(3))


def test_train_steps_chained_guard_skips_poisoned_step(devices):
    """A NaN batch mid-window: the guard drops that step's update INSIDE the
    chain (per-step nonfinite scan outputs flag exactly it) and the result
    equals the sequential run on the same poisoned stream."""
    host = [synthetic_batch(16, seed=20 + i) for i in range(4)]
    host[2] = dict(host[2], image=np.full_like(host[2]["image"], np.nan))
    eng_a, state_a = make_engine(nan_guard=True)
    eng_b, state_b = make_engine(nan_guard=True)
    for hb in host:
        state_a, _ = eng_a.train_step(state_a, eng_a.shard_batch(hb))
    gb = mesh_lib.global_chain_array_from_host_local(stack_batches(host), eng_b.mesh)
    state_b, stacked = eng_b.train_steps_chained(state_b, gb, 4)
    np.testing.assert_array_equal(
        np.asarray(stacked["nonfinite"]), np.array([0.0, 0.0, 1.0, 0.0])
    )
    assert_trees_equal(state_a.params, state_b.params)
    for leaf in jax.tree.leaves(state_b.params):
        assert np.isfinite(np.asarray(leaf)).all()
    # step still advanced past the poison (data/dropout streams move on)
    assert int(state_b.step) == 4


def test_train_steps_chained_compiles_once_per_length(devices):
    """The retrace guard's engine contract: repeated windows of one length
    trace exactly once (jit cache hit), a second length traces separately."""
    eng, state = make_engine()
    host = [synthetic_batch(16, seed=30 + i) for i in range(2)]
    gb = mesh_lib.global_chain_array_from_host_local(stack_batches(host), eng.mesh)
    for _ in range(3):
        state, _ = eng.train_steps_chained(state, gb, 2)
    assert eng.trace_counts["chained_2"] == 1
    host3 = [synthetic_batch(16, seed=40 + i) for i in range(3)]
    gb3 = mesh_lib.global_chain_array_from_host_local(stack_batches(host3), eng.mesh)
    state, _ = eng.train_steps_chained(state, gb3, 3)
    assert eng.trace_counts["chained_3"] == 1
    assert eng.trace_counts["chained_2"] == 1
    with pytest.raises(ValueError, match="length must be >= 1"):
        eng.train_steps_chained(state, gb, 0)


def test_unstack_window_matches_individual_batches(devices):
    eng, state = make_engine()
    host = [synthetic_batch(16, seed=50 + i) for i in range(2)]
    gb = mesh_lib.global_chain_array_from_host_local(stack_batches(host), eng.mesh)
    for i, hb in enumerate(host):
        single = eng.unstack_window(gb, i)
        expect = eng.shard_batch(hb)
        assert_trees_equal(single, expect)
        assert single["image"].sharding == expect["image"].sharding


# ---------------------------------------------------------------------------
# Prefetch: chain-major staging.


def _loader(n, batch, mesh_unused=None):
    images, labels = synthetic_images(n, seed=3)
    return ShardedLoader(
        ArrayDataSource(image=images, label=labels),
        batch,
        shuffle=False,
        num_workers=0,
    )


def test_device_prefetch_chained_units_and_values(devices):
    """lead singles + full windows + tail singles, values identical to the
    plain batch stream."""
    mesh = mesh_lib.create_mesh()
    loader = _loader(88, 8)  # 11 batches
    units = list(
        device_prefetch_chained(iter(loader), mesh, 4, lead_singles=2)
    )
    assert [n for n, _ in units] == [1, 1, 4, 4, 1]
    flat = []
    for n, b in units:
        if n == 1:
            flat.append(jax.device_get(b))
        else:
            host = jax.device_get(b)
            for i in range(n):
                flat.append(jax.tree.map(lambda x, i=i: x[i], host))
    plain = [dict(b) for b in loader]
    assert len(flat) == len(plain) == 11
    for got, want in zip(flat, plain, strict=True):
        np.testing.assert_array_equal(got["image"], np.asarray(want["image"]))
        np.testing.assert_array_equal(got["label"], np.asarray(want["label"]))


def test_device_prefetch_chained_degenerate_single(devices):
    mesh = mesh_lib.create_mesh()
    loader = _loader(24, 8)
    units = list(device_prefetch_chained(iter(loader), mesh, 1))
    assert [n for n, _ in units] == [1, 1, 1]


def test_device_prefetch_chained_rejects_bad_chain(devices):
    mesh = mesh_lib.create_mesh()
    with pytest.raises(ValueError, match="chain_steps"):
        device_prefetch_chained(iter([]), mesh, 0)


def test_device_prefetch_abandoned_consumer_shuts_down(devices):
    """Abandoning the iterator mid-stream must terminate the producer thread
    and release queued device buffers (the hardened shutdown drain)."""
    import threading
    import time

    mesh = mesh_lib.create_mesh()
    loader = _loader(80, 8)
    it = device_prefetch_chained(iter(loader), mesh, 2, depth=2)
    next(it)
    it.close()  # runs the generator's finally: cancel, drain, join, re-drain
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline:
        if not any(t.name == "device-prefetch" for t in threading.enumerate()):
            break
        time.sleep(0.05)
    assert not any(t.name == "device-prefetch" for t in threading.enumerate())


# ---------------------------------------------------------------------------
# Trainer: windowed hot loop — bit-exact parity, tails, fallbacks, validation.


TRAIN_KW = dict(max_epoch=2, have_validate=False, save_best_for=None, save_period=None)


@pytest.fixture(scope="module")
def single_run(tmp_path_factory, mesh):
    """The chain_steps=1 baseline every parity assertion compares against."""
    t = make_trainer(
        tmp_path_factory.mktemp("single"), mesh, cls=RecordingToyTrainer, **TRAIN_KW
    )
    t.epoch_metrics = []
    t.train()
    return t


@pytest.fixture(scope="module")
def chained_run(tmp_path_factory, mesh):
    """chain_steps=4 over 4 steps/epoch: every step of every epoch chained."""
    t = make_trainer(
        tmp_path_factory.mktemp("chained"),
        mesh,
        cls=RecordingToyTrainer,
        chain_steps=4,
        **TRAIN_KW,
    )
    t.epoch_metrics = []
    t.train()
    return t


def test_trainer_chained_bit_exact_params_and_metrics(single_run, chained_run):
    """chain_steps=4 == chain_steps=1: the same step count and epoch metrics
    (equal on every seed measured), params and opt_state within
    CHAINED_VS_SINGLE_ULPS."""
    assert int(chained_run.state.step) == int(single_run.state.step) == 8
    assert_trees_within_ulps(
        single_run.state.params, chained_run.state.params, CHAINED_VS_SINGLE_ULPS
    )
    assert_trees_within_ulps(
        single_run.state.opt_state, chained_run.state.opt_state, CHAINED_VS_SINGLE_ULPS
    )
    assert len(single_run.epoch_metrics) == len(chained_run.epoch_metrics) == 2
    for ma, mb in zip(single_run.epoch_metrics, chained_run.epoch_metrics, strict=True):
        assert set(ma) == set(mb)
        for k in ma:
            assert ma[k] == mb[k], (k, ma, mb)


def test_trainer_chained_actually_chained(chained_run):
    """Guards against silently falling back to per-step dispatch: with 4
    steps/epoch and chain_steps=4, the single-step executable is never built
    — every step ran inside the chained program."""
    assert chained_run.engine.trace_counts["chained_4"] == 1
    assert chained_run.engine.trace_counts["train_step"] == 0


def test_trainer_chained_tail_falls_back_single_step(single_run, tmp_path, mesh):
    """chain_steps=3 over 4 steps/epoch: one window + one tail single per
    epoch, params still within CHAINED_VS_SINGLE_ULPS of the single-step
    run's, and no per-tail-length chain is compiled."""
    t = make_trainer(tmp_path, mesh, chain_steps=3, **TRAIN_KW)
    t.train()
    assert int(t.state.step) == int(single_run.state.step)
    assert_trees_within_ulps(single_run.state.params, t.state.params, CHAINED_VS_SINGLE_ULPS)
    assert t.engine.trace_counts["chained_3"] == 1
    assert t.engine.trace_counts["train_step"] == 1
    assert set(t.engine._chained_fns) == {3}


@pytest.fixture(scope="module")
def nan_plan_runs(tmp_path_factory, mesh):
    """nan_policy='skip' + injected NaN at (epoch 0, step 1), chained vs
    single. The injection window [0,4) of epoch 0 runs single-step (fault
    fallback); epoch 1 chains — parity must survive the mode switches."""
    runs = []
    for chain in (1, 4):
        plan = FaultPlan().add("nan_loss", epoch=0, step=1)
        t = make_trainer(
            tmp_path_factory.mktemp(f"nan{chain}"),
            mesh,
            chain_steps=chain,
            nan_policy="skip",
            fault_plan=plan,
            **TRAIN_KW,
        )
        t.train()
        runs.append(t)
    return runs


def test_trainer_chained_nan_policy_skip_parity(nan_plan_runs):
    single, chained = nan_plan_runs
    assert single.nonfinite_steps == chained.nonfinite_steps == 1
    assert single.fault_plan.count_fired("nan_loss") == 1
    assert chained.fault_plan.count_fired("nan_loss") == 1
    assert_trees_equal(single.state.params, chained.state.params)
    for leaf in jax.tree.leaves(chained.state.params):
        assert np.isfinite(np.asarray(leaf)).all()
    # the fault-active window ran single-step; the clean epoch chained
    assert chained.engine.trace_counts["train_step"] == 1
    assert chained.engine.trace_counts["chained_4"] == 1


# ---------------------------------------------------------------------------
# Config validation: incompatible knobs fail loudly at construction.


def test_chain_steps_must_divide_log_every(tmp_path, mesh):
    with pytest.raises(ValueError, match="log_every"):
        make_trainer(tmp_path, mesh, chain_steps=4, log_every=6, **TRAIN_KW)


def test_chain_steps_rejects_nonpositive(tmp_path, mesh):
    with pytest.raises(ValueError, match="chain_steps must be >= 1"):
        make_trainer(tmp_path, mesh, chain_steps=0, **TRAIN_KW)


def test_chain_steps_rejects_custom_train_step(tmp_path, mesh):
    class CustomStep(ToyTrainer):
        def train_step(self, state, batch):
            return super().train_step(state, batch)

    with pytest.raises(ValueError, match="overrides train_step"):
        make_trainer(tmp_path, mesh, cls=CustomStep, chain_steps=4, **TRAIN_KW)


def test_preemption_cadence_rounded_to_window_boundary(tmp_path, mesh):
    t = make_trainer(
        tmp_path, mesh, chain_steps=4, preemption_check_every=10, **TRAIN_KW
    )
    assert t.preemption_check_every == 12
