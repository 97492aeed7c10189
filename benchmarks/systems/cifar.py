"""``examples/train_cifar10.py``'s ``Cifar10Trainer`` with a CIFAR-shaped set
from the seed (the entry's own synthetic set has a fixed seed) and the
configuration's dropout. Everything else is the entry's: full-width VGG16 in
bf16, SGD momentum with weight decay, warm-up + cosine, the native crop/flip
input path and on-device normalisation."""

from __future__ import annotations

import jax.numpy as jnp

from benchmarks.systems.common import StepLosses, trainer_kwargs
from distributed_training_pytorch_tpu.trainer import Trainer
from examples import train_cifar10


class BenchCifar10Trainer(StepLosses, train_cifar10.Cifar10Trainer):
    def __init__(self, images, labels, base_lr: float, dims: dict, **kw):
        # Cifar10Trainer.__init__ minus load_cifar10.
        self.train_x, self.train_y = images, labels
        self.test_x, self.test_y = images[:1], labels[:1]
        self.base_lr, self.dims = base_lr, dims
        kw.setdefault("precision", train_cifar10.DTYPE)
        Trainer.__init__(self, **kw)

    def build_model(self):
        from distributed_training_pytorch_tpu.models import InputNormalizer, create_model
        from distributed_training_pytorch_tpu.precision import model_dtype_for_entry

        model = create_model(
            "vgg16",
            num_classes=10,
            dtype=model_dtype_for_entry(
                self.precision, train_cifar10.DTYPE is not None or self.precision_requested, jnp.bfloat16
            ),
            pallas=train_cifar10.PALLAS,
            **self.dims,  # the configuration file's widths (VGG16's defaults) and dropout
        )
        if self._device_normalize:
            model = InputNormalizer(
                model, mean=tuple(train_cifar10.CIFAR_MEAN), std=tuple(train_cifar10.CIFAR_STD))
        return model


def prepare() -> None:
    pass


def build(cfg: dict, traffic: dict, data: dict, **common):
    from distributed_training_pytorch_tpu.data import native

    if not native.available():
        raise RuntimeError("the native C++ crop/flip input runtime did not build; this cell times that path")
    # The entry scales base_lr by batch / 256; the configuration states the peak.
    base_lr = cfg["optimizer"]["lr"] * 256.0 / traffic["global_batch"]
    return BenchCifar10Trainer(
        data["images"], data["labels"], base_lr,
        dict(stage_features=tuple(cfg["stage_features"]), stage_layers=tuple(cfg["stage_layers"]),
             classifier_widths=tuple(cfg["classifier_widths"]), dropout_rate=cfg["dropout"]),
        **trainer_kwargs(cfg, traffic, **common),
    )


def expect_kernels(cfg: dict, on_tpu: bool) -> list[str]:
    return []
