import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import linen as nn

from distributed_training_pytorch_tpu.ops import cross_entropy_loss, accuracy, multistep_lr
from distributed_training_pytorch_tpu.parallel import mesh as mesh_lib
from distributed_training_pytorch_tpu.train import TrainEngine, make_supervised_loss


class TinyMLP(nn.Module):
    num_classes: int = 3

    @nn.compact
    def __call__(self, x, *, train: bool = False):
        x = x.reshape(x.shape[0], -1)
        x = nn.Dense(32)(x)
        x = nn.relu(x)
        return nn.Dense(self.num_classes)(x)


def criterion(logits, batch):
    loss = cross_entropy_loss(logits, batch["label"])
    return loss, {"ce_loss": loss, "accuracy": accuracy(logits, batch["label"])}


def make_engine(accum_steps=1, schedule=None):
    mesh = mesh_lib.create_mesh()
    model = TinyMLP()
    tx = optax.sgd(schedule if schedule else 0.05, momentum=0.9)
    engine = TrainEngine(
        make_supervised_loss(model, criterion),
        tx,
        mesh,
        accum_steps=accum_steps,
        schedule=schedule,
    )
    state = engine.init_state(
        jax.random.key(0), lambda rng: model.init(rng, jnp.zeros((1, 4, 4, 3)))
    )
    return engine, state


# A chained window against the same steps run singly. The two programs hold
# the same arithmetic, but on the CPU backend an unrolled window and a
# standalone step may order a reduction (the sum inside a conv or matmul
# gradient) or contract a multiply-add differently, so float results land
# within a few units in the last place of each leaf's largest value, not on
# the same bits. Measured over 8
# data seeds (engine level) and 5 trainer seeds: at most 6 in the optimizer
# state, 3.5 in the params, 2 in a per-step metric; the bound is the largest
# doubled. Not checked on the TPU. Step counts, integer leaves and whatever
# measured equal on every seed are still compared exactly.
CHAINED_VS_SINGLE_ULPS = 12


def ulp_distance(x, y) -> float:
    """Largest elementwise gap between two arrays of one float dtype, in units
    in the last place of the arrays' largest magnitude (0 = bit-equal). The
    leaf's scale and not each element's: an element near zero that is the sum
    of large terms carries their rounding error, many ULPs of its own size."""
    x, y = np.asarray(x), np.asarray(y)
    assert x.dtype == y.dtype and x.shape == y.shape, (x.dtype, y.dtype, x.shape, y.shape)
    gap = float(np.max(np.abs(x.astype(np.float64) - y.astype(np.float64)), initial=0.0))
    if gap == 0.0:
        return 0.0
    scale = float(max(np.max(np.abs(x)), np.max(np.abs(y))))
    return gap / 2.0 ** (np.floor(np.log2(scale)) - jnp.finfo(x.dtype).nmant)


def assert_trees_within_ulps(a, b, max_ulps: int):
    """Float leaves within ``max_ulps`` representable values of each other;
    every other leaf (step counts, integer state) equal."""
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb, strict=True):
        x, y = np.asarray(x), np.asarray(y)
        if jnp.issubdtype(x.dtype, jnp.floating):
            assert ulp_distance(x, y) <= max_ulps, (ulp_distance(x, y), max_ulps, x.shape)
        else:
            np.testing.assert_array_equal(x, y)


def synthetic_batch(n=16, seed=0):
    rng = np.random.RandomState(seed)
    labels = rng.randint(0, 3, size=(n,)).astype(np.int32)
    # class-dependent mean makes the task learnable
    images = rng.randn(n, 4, 4, 3).astype(np.float32) + labels[:, None, None, None]
    return {"image": images, "label": labels}


def test_train_step_runs_and_loss_decreases(devices):
    engine, state = make_engine()
    batch = engine.shard_batch(synthetic_batch())
    losses = []
    for _ in range(30):
        state, metrics = engine.train_step(state, batch)
        losses.append(float(metrics["ce_loss"]))
    assert losses[-1] < losses[0] * 0.5, losses
    assert int(state.step) == 30


def test_eval_step_metrics(devices):
    engine, state = make_engine()
    batch = engine.shard_batch(synthetic_batch())
    for _ in range(50):
        state, step_metrics = engine.train_step(state, batch)
        # One step in flight at a time: 50 unsynced dispatches of an 8-device
        # all-reduce can wedge the CPU backend's in-process rendezvous on a
        # loaded machine (7 of 8 participants arrive; abort after 40 s).
        jax.block_until_ready(step_metrics)
    metrics = engine.eval_step(state, batch)
    assert float(metrics["accuracy"]) > 0.8


def test_grad_accum_matches_full_batch(devices):
    # Same data, same init: accum_steps=4 must equal accum_steps=1 with SGD
    batch_np = synthetic_batch(32)
    engine1, state1 = make_engine(accum_steps=1)
    engine4, state4 = make_engine(accum_steps=4)
    b1 = engine1.shard_batch(batch_np)
    b4 = engine4.shard_batch(batch_np)
    for _ in range(3):
        state1, m1 = engine1.train_step(state1, b1)
        state4, m4 = engine4.train_step(state4, b4)
    for p1, p4 in zip(jax.tree.leaves(state1.params), jax.tree.leaves(state4.params), strict=True):
        np.testing.assert_allclose(np.asarray(p1), np.asarray(p4), rtol=2e-4, atol=2e-5)


def test_schedule_reported_and_applied(devices):
    sched = multistep_lr(0.1, milestones=[1], gamma=0.1, steps_per_epoch=2)
    engine, state = make_engine(schedule=sched)
    batch = engine.shard_batch(synthetic_batch())
    _, m0 = engine.train_step(state, batch)
    assert np.isclose(float(m0["lr"]), 0.1)
    assert np.isclose(float(sched(2)), 0.01)


def test_determinism_same_seed(devices):
    engine_a, state_a = make_engine()
    engine_b, state_b = make_engine()
    batch = engine_a.shard_batch(synthetic_batch())
    for _ in range(3):
        state_a, _ = engine_a.train_step(state_a, batch)
        state_b, _ = engine_b.train_step(state_b, batch)
    for pa, pb in zip(jax.tree.leaves(state_a.params), jax.tree.leaves(state_b.params), strict=True):
        np.testing.assert_array_equal(np.asarray(pa), np.asarray(pb))


def test_state_sharding_rejects_foreign_state(devices):
    """Regression: a reused engine applied the FIRST state's
    cached sharding tree to any later state; now a different tree structure
    raises instead of mis-sharding silently."""
    import pytest

    engine, state = make_engine()

    class OtherMLP(nn.Module):
        @nn.compact
        def __call__(self, x, *, train: bool = False):
            x = x.reshape(x.shape[0], -1)
            x = nn.Dense(8)(x)
            x = nn.Dense(16)(x)  # extra layer -> different param tree
            return nn.Dense(3)(x)

    other = OtherMLP()
    with pytest.raises(ValueError, match="different structure or leaf shapes"):
        engine.init_state(
            jax.random.key(1), lambda rng: other.init(rng, jnp.zeros((1, 4, 4, 3)))
        )

    class SameTreeDifferentWidth(nn.Module):
        # same layer count as TinyMLP -> identical tree STRUCTURE, different
        # leaf shapes; must still be rejected.
        @nn.compact
        def __call__(self, x, *, train: bool = False):
            x = x.reshape(x.shape[0], -1)
            x = nn.Dense(64)(x)
            x = nn.relu(x)
            return nn.Dense(3)(x)

    widened = SameTreeDifferentWidth()
    with pytest.raises(ValueError, match="different structure or leaf shapes"):
        engine.init_state(
            jax.random.key(2), lambda rng: widened.init(rng, jnp.zeros((1, 4, 4, 3)))
        )
    # The original state keeps working.
    batch = engine.shard_batch(synthetic_batch())
    state, metrics = engine.train_step(state, batch)
    assert np.isfinite(float(metrics["ce_loss"]))


def test_chained_steps_match_sequential(devices):
    """compile_chained_train_steps(K) == K sequential train_steps (same RNG
    advance via state.step, same params) — the bench's one-dispatch window."""
    batch_np = synthetic_batch(16)
    eng_a, state_a = make_engine()
    eng_b, state_b = make_engine()
    ba = eng_a.shard_batch(batch_np)
    bb = eng_b.shard_batch(batch_np)
    for _ in range(4):
        state_a, m_a = eng_a.train_step(state_a, ba)
    chained = eng_b.compile_chained_train_steps(state_b, bb, 4)
    state_b, m_b = chained(state_b, bb)
    assert int(state_b.step) == int(state_a.step) == 4
    np.testing.assert_allclose(float(m_b["ce_loss"]), float(m_a["ce_loss"]), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(state_a.params), jax.tree.leaves(state_b.params), strict=True):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)
