"""Model-zoo tests: parameter-count parity with the canonical torch
implementations (shape-level, via eval_shape — no big allocations), forward
shapes, and compiled train-step smoke on the 8-device mesh (SURVEY.md §7
step 8 / BASELINE configs 3-5)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from distributed_training_pytorch_tpu.models import (
    ConvNeXtL,
    ConvNeXtTiny,
    ResNet18Slim,
    ResNet50,
    ViTB16,
    ViTTiny,
    create_model,
)
from distributed_training_pytorch_tpu.ops import accuracy, cross_entropy_loss
from distributed_training_pytorch_tpu.parallel import mesh as mesh_lib
from distributed_training_pytorch_tpu.train import TrainEngine, make_supervised_loss


def param_count(model, input_shape):
    shapes = jax.eval_shape(
        lambda rng: model.init(rng, jnp.zeros(input_shape)), jax.random.key(0)
    )
    return sum(int(np.prod(l.shape)) for l in jax.tree.leaves(shapes["params"]))


def test_resnet50_param_count():
    # torchvision resnet50(num_classes=1000): 25,557,032 params.
    assert param_count(ResNet50(1000), (1, 224, 224, 3)) == 25_557_032


def test_vit_b16_param_count():
    # timm vit_base_patch16_224 (cls token + learned pos embed, qkv bias):
    # 86,567,656 params.
    assert param_count(ViTB16(1000), (1, 224, 224, 3)) == 86_567_656


def test_convnext_l_param_count():
    # Official ConvNeXt-L @1k: 197,767,336 params.
    assert param_count(ConvNeXtL(num_classes=1000), (1, 224, 224, 3)) == 197_767_336


def test_create_model_factory():
    assert create_model("resnet50", 10).num_classes == 10
    assert create_model("vit-b/16", 10).num_classes == 10
    assert create_model("convnext-l", 10).num_classes == 10
    assert create_model("vgg16", 10).num_classes == 10
    with pytest.raises(ValueError):
        create_model("alexnet", 10)


def _smoke(model, mesh, image_size=32, num_classes=10, has_model_state=False):
    def criterion(logits, batch):
        loss = cross_entropy_loss(logits, batch["label"])
        return loss, {"loss": loss, "accuracy": accuracy(logits, batch["label"])}

    engine = TrainEngine(
        make_supervised_loss(model, criterion), optax.sgd(0.01, momentum=0.9), mesh
    )
    state = engine.init_state(
        jax.random.key(0),
        lambda rng: model.init(rng, jnp.zeros((1, image_size, image_size, 3))),
    )
    rng = np.random.RandomState(0)
    batch = engine.shard_batch(
        {
            "image": rng.randn(16, image_size, image_size, 3).astype(np.float32),
            "label": rng.randint(0, num_classes, size=(16,)).astype(np.int32),
        }
    )
    # The engine donates the input state; snapshot stats before stepping.
    old = jax.device_get(state.model_state) if has_model_state else None
    new_state, metrics = engine.train_step(state, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert int(new_state.step) == 1
    if has_model_state:
        new = jax.device_get(new_state.model_state)
        assert any(
            not np.allclose(a, b)
            for a, b in zip(jax.tree.leaves(old), jax.tree.leaves(new), strict=True)
        ), "batch_stats must update during training"
    return new_state


@pytest.fixture
def mesh(devices):
    return mesh_lib.create_mesh({mesh_lib.DATA_AXIS: 8}, devices=devices)


@pytest.mark.slow
def test_resnet_train_step_updates_batch_stats(mesh):
    _smoke(ResNet18Slim(num_classes=10), mesh, has_model_state=True)


def test_vit_train_step(mesh):
    _smoke(ViTTiny(num_classes=10), mesh)


@pytest.mark.slow
def test_convnext_train_step_with_droppath(mesh):
    _smoke(ConvNeXtTiny(num_classes=10, drop_path_rate=0.2), mesh)


@pytest.mark.slow  # moved out of tier-1 to keep it inside its cap (PR 21)
def test_resnet_eval_deterministic(mesh):
    """Eval mode uses running stats — two eval calls agree, and differ from
    train-mode output."""
    model = ResNet18Slim(num_classes=10)
    # jitted: un-jitted op-by-op apply costs ~25s of suite time on CPU.
    variables = jax.jit(model.init)(jax.random.key(0), jnp.zeros((1, 32, 32, 3)))
    x = jnp.asarray(np.random.RandomState(0).randn(4, 32, 32, 3), jnp.float32)
    eval_fn = jax.jit(lambda v, x: model.apply(v, x, train=False))
    e1 = eval_fn(variables, x)
    e2 = eval_fn(variables, x)
    np.testing.assert_array_equal(np.asarray(e1), np.asarray(e2))


def test_vit_rejects_bad_patch_grid():
    model = ViTTiny()
    with pytest.raises(ValueError, match="not divisible"):
        model.init(jax.random.key(0), jnp.zeros((1, 30, 30, 3)))


def test_droppath_zero_at_eval():
    """drop_path is identity at eval; train mode with rate ~1 kills the branch."""
    from distributed_training_pytorch_tpu.models.convnext import DropPath

    x = jnp.ones((4, 3))
    mod = DropPath(0.99)
    out = mod.apply({}, x, train=False)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x))


def test_input_normalizer_uint8_vs_float_dispatch():
    """InputNormalizer normalizes uint8 batches on device and passes float
    batches through untouched (they arrive pre-normalized, e.g. from the
    native val decode) — the mixed uint8-train / f32-val contract of
    examples/train_imagenet.py SHIP_UINT8."""
    from flax import linen as nn

    from distributed_training_pytorch_tpu.models.wrappers import InputNormalizer

    class Echo(nn.Module):
        @nn.compact
        def __call__(self, x, *, train=False):
            return x

    mean, std = [0.5, 0.5, 0.5], [0.25, 0.25, 0.25]
    model = InputNormalizer(inner=Echo(), mean=mean, std=std)
    rng = np.random.RandomState(0)
    raw = rng.randint(0, 256, size=(2, 4, 4, 3)).astype(np.uint8)
    variables = model.init(jax.random.key(0), jnp.asarray(raw))
    out_u8 = model.apply(variables, jnp.asarray(raw))
    expect = (raw.astype(np.float32) / 255.0 - np.asarray(mean)) / np.asarray(std)
    np.testing.assert_allclose(np.asarray(out_u8), expect, atol=1e-6)
    pre = jnp.asarray(expect)
    out_f32 = model.apply(variables, pre)
    np.testing.assert_array_equal(np.asarray(out_f32), np.asarray(pre))
