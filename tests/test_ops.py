import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from sklearn.metrics import top_k_accuracy_score

from distributed_training_pytorch_tpu.ops import (
    cross_entropy_loss,
    multistep_lr,
    top_k_accuracy,
    warmup_cosine_lr,
)
from distributed_training_pytorch_tpu.ops.losses import (
    softmax_cross_entropy_with_integer_labels,
)


def test_cross_entropy_matches_manual():
    logits = jnp.asarray([[2.0, 0.0, -1.0], [0.0, 0.0, 0.0]])
    labels = jnp.asarray([0, 2])
    per_ex = softmax_cross_entropy_with_integer_labels(logits, labels)
    expected0 = -np.log(np.exp(2.0) / (np.exp(2.0) + 1 + np.exp(-1.0)))
    np.testing.assert_allclose(np.asarray(per_ex), [expected0, np.log(3.0)], rtol=1e-6)
    np.testing.assert_allclose(
        float(cross_entropy_loss(logits, labels)), (expected0 + np.log(3.0)) / 2, rtol=1e-6
    )


def test_label_smoothing_increases_loss_on_confident_preds():
    logits = jnp.asarray([[10.0, 0.0, 0.0]])
    labels = jnp.asarray([0])
    plain = float(cross_entropy_loss(logits, labels))
    smoothed = float(cross_entropy_loss(logits, labels, label_smoothing=0.1))
    assert smoothed > plain


def test_top_k_accuracy_matches_sklearn():
    rng = np.random.RandomState(0)
    scores = rng.randn(64, 5)
    labels = rng.randint(0, 5, size=64)
    for k in (1, 2, 3):
        ours = float(top_k_accuracy(jnp.asarray(scores), jnp.asarray(labels), k=k))
        ref = top_k_accuracy_score(labels, scores, k=k, labels=np.arange(5))
        np.testing.assert_allclose(ours, ref, rtol=1e-6)


def test_multistep_lr_matches_reference_schedule():
    # example_trainer.py:66 — MultiStepLR milestones [50,100,200], gamma 0.1
    sched = multistep_lr(0.1, [50, 100, 200], 0.1, steps_per_epoch=10)
    assert np.isclose(float(sched(0)), 0.1)
    assert np.isclose(float(sched(499)), 0.1)
    assert np.isclose(float(sched(500)), 0.01)
    assert np.isclose(float(sched(1000)), 0.001)
    assert np.isclose(float(sched(2000)), 1e-4)


def test_warmup_cosine_endpoints():
    sched = warmup_cosine_lr(1.0, total_epochs=10, steps_per_epoch=10, warmup_epochs=2)
    assert float(sched(0)) < 1e-6
    assert np.isclose(float(sched(20)), 1.0, atol=1e-3)
    assert float(sched(100)) < 1e-3


def _naive_tied_loss(hidden, emb, targets, weights):
    """The full-logits float32 loss the fused head must equal."""
    from distributed_training_pytorch_tpu.ops.losses import weighted_mean

    logits = jnp.einsum("btd,vd->btv", hidden.astype(jnp.float32), emb.astype(jnp.float32))
    nll = softmax_cross_entropy_with_integer_labels(logits, targets)
    return weighted_mean(nll.mean(axis=-1), weights)


# (B, T) of the operands, the mask, the operands' dtype, tokens a row the slice
# budget admits (None: the module's own, one slice here) with the slice length
# that gives, the cotangent
TIED_HEAD_CASES = {
    "no_mask": ((3, 4), None, jnp.float32, (2, 2), 1.0),
    "one_slice": ((3, 4), None, jnp.float32, (None, 4), 1.0),
    "mask_with_a_zero_row": ((3, 4), (1.0, 0.0, 0.5), jnp.float32, (2, 2), 1.0),
    "all_zero_mask": ((3, 4), (0.0, 0.0, 0.0), jnp.float32, (2, 2), 1.0),
    "seq_len_1": ((5, 1), (1.0, 1.0, 0.0, 1.0, 1.0), jnp.float32, (2, 1), 1.0),
    "prime_seq_len": ((2, 13), None, jnp.float32, (4, 1), 1.0),  # 13's divisors are 1 and itself
    "nothing_fits": ((2, 6), None, jnp.float32, (0, 1), 1.0),
    "bf16_operands": ((3, 4), (1.0, 0.0, 1.0), jnp.bfloat16, (2, 2), 1.0),
    "cotangent_1024": ((3, 4), (1.0, 0.0, 1.0), jnp.float32, (2, 2), 1024.0),
}


@pytest.mark.parametrize("case", TIED_HEAD_CASES)
def test_tied_cross_entropy_matches_naive(case, monkeypatch):
    """The sliced tied head == the naive full-logits loss in float32: the
    undifferentiated value, the loss and both gradients."""
    from distributed_training_pytorch_tpu.ops import losses

    (b, t), mask, dtype, (fits, want_slice), cotangent = TIED_HEAD_CASES[case]
    d, v = 8, 37  # a vocabulary that is no multiple of the lane width
    if fits is not None:
        monkeypatch.setattr(losses, "_SLICE_LOGITS_BYTES", 4 * b * v * fits)
    assert losses._slice_len(b, t, v) == want_slice
    rng = np.random.RandomState(0)
    hidden = jnp.asarray(rng.randn(b, t, d), dtype)
    emb = jnp.asarray(rng.randn(v, d) * 0.3, dtype)
    targets = jnp.asarray(rng.randint(0, v, size=(b, t)), jnp.int32)
    weights = None if mask is None else jnp.asarray(mask, jnp.float32)

    def fused(h, e):
        return losses.tied_cross_entropy_loss(h, e, targets, weights) * cotangent

    def naive(h, e):
        return _naive_tied_loss(h, e, targets, weights) * cotangent

    # float32: rounding only; bf16: the gradients come back as bf16 (2**-8)
    rtol, atol = (1e-5, 1e-6 * cotangent) if dtype == jnp.float32 else (1e-2, 1e-4)
    want = naive(hidden, emb)
    np.testing.assert_allclose(float(fused(hidden, emb)), float(want), rtol=1e-5, atol=1e-6)
    loss, grads = jax.value_and_grad(fused, argnums=(0, 1))(hidden, emb)
    np.testing.assert_allclose(float(loss), float(want), rtol=1e-5, atol=1e-6)
    if case == "all_zero_mask":
        assert float(loss) == 0.0
    for got, ref in zip(grads, jax.grad(naive, argnums=(0, 1))(hidden, emb), strict=True):
        assert got.dtype == dtype and got.shape == ref.shape
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(ref, np.float32), rtol=rtol, atol=atol
        )


def test_tied_cross_entropy_under_a_data_mesh_exchanges_once(devices, monkeypatch):
    """``data=4``: the loss and gradients of one device, and the embedding's
    gradient exchanged once after the loop, not once a slice inside it."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from distributed_training_pytorch_tpu.analysis import hlo_audit
    from distributed_training_pytorch_tpu.ops import losses
    from distributed_training_pytorch_tpu.parallel import mesh as mesh_lib

    b, t, d, v = 8, 16, 32, 300
    monkeypatch.setattr(losses, "_SLICE_LOGITS_BYTES", 4 * 2 * v * 4)  # 2 rows a chip x 4 tokens
    rng = np.random.RandomState(1)
    hidden = jnp.asarray(rng.randn(b, t, d), jnp.float32)
    emb = jnp.asarray(rng.randn(v, d) * 0.3, jnp.float32)
    targets = jnp.asarray(rng.randint(0, v, size=(b, t)), jnp.int32)
    weights = jnp.asarray([1.0, 1.0, 0.0, 1.0, 0.5, 1.0, 1.0, 1.0], jnp.float32)
    fn = jax.value_and_grad(losses.tied_cross_entropy_loss, argnums=(0, 1))
    want_loss, want_grads = fn(hidden, emb, targets, weights)

    mesh = mesh_lib.create_mesh({mesh_lib.DATA_AXIS: 4}, devices=devices[:4])
    rows, whole = NamedSharding(mesh, P(mesh_lib.DATA_AXIS)), NamedSharding(mesh, P())
    operands = jax.device_put((hidden, emb, targets, weights), (rows, whole, rows, rows))
    with jax.sharding.set_mesh(mesh):
        assert mesh_lib.ambient_batch_axes(b) == ((mesh_lib.DATA_AXIS,), 4)  # a slice is sized by a chip's rows
        compiled = jax.jit(fn, out_shardings=(whole, (rows, whole))).lower(*operands).compile()
    loss, grads = compiled(*operands)
    np.testing.assert_allclose(float(loss), float(want_loss), rtol=1e-6)
    for got, ref in zip(grads, want_grads, strict=True):
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-5, atol=1e-7)
    comps = hlo_audit.computations(compiled.as_text())
    loops = hlo_audit.called_from(comps, lambda ln: " while(" in ln)
    assert loops, "the compiled head has no loop"
    exchanges = [name in loops for name, lines in comps.items() for ln in lines
                 if re.search(r" all-reduce(-start)?\(", ln)]
    assert exchanges and not any(exchanges), exchanges  # after the loop: the embedding's gradient
